"""Multi-master epoch replication engine (GeoGauss-like) + Raft-plane model.

This is the end-to-end database plane the macro benchmarks (paper Fig. 11,
14, 17, 18, Table 1) run on.  Per epoch (default cadence 10 ms, the GeoGauss
setting):

1. every replica executes its transaction batch locally (OCC, Sec 4.3),
2. write sets are synchronized — flat all-to-all (baseline) or GeoCoCo's
   hierarchical schedule with aggregator-side white-data filtering,
3. deterministic global validation commits the epoch and all replicas merge
   the committed deltas (CRDT join), producing identical state everywhere.

Throughput model — two regimes:

* **formula pipelining** (``EngineConfig.streaming=False``, the historical
  model): epochs overlap only arithmetically — the epoch wall-clock is
  ``max(epoch_cadence, execution, synchronization)`` (execution of epoch
  e+1 is assumed to hide under the synchronization of epoch e), and
  synchronization becomes the bottleneck exactly when WAN latency/bandwidth
  dominate (Fig. 3).
* **streaming simulation** (``streaming=True``): consecutive epochs' DAGs
  are *stitched* (:func:`~repro.core.schedule.stitch_schedules`) — epoch
  e+1's gathers out of node s depend only on s's epoch-e commit, per-node
  transaction execution and the epoch cadence ride the DAG as local compute
  stages — and one event-driven simulation measures real per-epoch commit
  times.  Epoch e+1's gathers genuinely stream under epoch e's scatters
  (they ride disjoint NIC directions), as GeoGauss streams multi-master
  state; ``EpochStats.wall_ms`` is the measured inter-commit gap and
  ``pipeline_overlap_ms`` is what the formula would have charged on top.
  Commit content is untouched (validation still waits for every epoch
  write set), so digests are byte-identical across both regimes.

  ``EngineConfig(staleness_feedback=True)`` (streaming only) additionally
  feeds the measured timing back into the OCC outcome: each replica keeps
  its own snapshot view, advanced only when the stitched simulation has
  delivered that node's inbound epoch transfers, and transactions version
  their reads against the executing node's view — so a node paying off a
  WAN backlog executes epoch ``e`` against an epoch ``e-k`` snapshot and
  read-validation aborts become a function of network conditions
  (timing-dependent commit by design; digests may diverge from the
  default engines, see ``EpochStats.read_aborts`` / ``view_lag_mean``).

Within an epoch the synchronization itself is pipelined too (the default,
``EngineConfig.barrier=False``): write-set rounds execute as an event-driven
transfer DAG where each group's aggregator-side filter/compress CPU time is
charged on that group's exchange transfers — so one group's CPU overlaps
other groups' in-flight WAN transfers, and ``sync_ms`` is the DAG critical
path rather than the barrier phase-sum.  Epoch commit still waits for the
*full* DAG to sink (every transfer delivered), so the committed state is
byte-identical to the barrier engine — :class:`EpochStats` reports the
hidden work as ``sync_overlap_ms = sync_serial_ms - sync_ms``.
``EngineConfig(barrier=True)`` restores the pre-DAG barrier engine exactly,
for regression comparison.

The :class:`RaftCluster` models the CockroachDB integration (Sec 5
"Extensions"): leader-based AppendEntries fan-out, commit at majority quorum,
with GeoCoCo optionally relaying through group aggregators.
"""

from __future__ import annotations

import collections
import dataclasses
import time
import zlib as _zlib
from typing import Callable, Sequence

import numpy as np

from . import strategies as _strategies
from .crdt import DeltaCRDTStore, Update
from .occ import Txn, txn_updates, validate_epoch_detailed
from .planner import GroupPlan, no_grouping
from .schedule import (
    TransmissionSchedule,
    all_to_all_schedule,
    hierarchical_schedule,
    leader_schedule,
    stitch_schedules,
)
from .simulator import EpochLatencyCycle, WANSimulator, node_commit_ms
from .sinks import EpochContext, EpochSink, RunAggregator, RunSummary
from .stream import StreamingTimeline
from .whitedata import FilterResult, FilterStats, filter_group_batch

# the serving plane lives above this engine (it consumes measured commit
# times, never feeds back into them); importing its config here keeps
# EngineConfig the single wiring surface, like staleness_feedback
from ..analysis.config_check import validate_config
from ..serve.config import ServeConfig
from ..serve.stats import ServeStats

__all__ = ["EngineConfig", "EpochStats", "RunStats", "GeoCluster",
           "RaftCluster", "advance_views"]


@dataclasses.dataclass
class EngineConfig:
    """Engine configuration with a named-strategy surface.

    ``sync_strategy`` names a registered ``wan_sync`` preset (``flat`` /
    ``hier`` / ``geococo`` / ``geococo-zlib`` — the same names the device
    plane's ``SyncConfig`` uses); when given it drives the per-stage
    booleans.  The booleans remain writable for back-compat (the original
    API) and for ablations without an exact preset — ``__post_init__``
    derives the nearest ``sync_strategy`` name from them.  ``schedule_name``
    and ``filter_name`` select registered implementations for the grouping
    transmission and the aggregator filter, so new builders and codecs plug
    in without touching this engine.
    """

    n_nodes: int
    epoch_ms: float = 10.0
    txn_exec_us: float = 40.0
    barrier: bool = False              # True = pre-DAG barrier-phase engine
    streaming: bool = False            # True = cross-epoch stitched simulation
    # feed measured per-node commit staleness back into the OCC abort model:
    # replicas execute each epoch against their *own* snapshot view, which
    # advances only when the stitched simulation delivered that node's
    # inbound epoch transfers — so read-set validation aborts become a
    # function of network conditions.  Timing-dependent commit by design:
    # the default (off) preserves the byte-identical-digest invariant
    # across barrier/event/streaming engines.
    staleness_feedback: bool = False
    # read serving plane (streaming only, default off): region-affine client
    # populations serve follower reads against the per-node stale views the
    # stitched simulation measures; results land on RunStats.serve.  Purely
    # observational — serving never changes which bytes commit, so digests
    # are unaffected.
    serve: ServeConfig | None = None
    # modeled bytes-proportional filter/compress CPU instead of measured
    # perf_counter wall-clock (opt-in): gated benchmarks whose metric rides
    # the simulated timeline (Fig16 stacking, abort-curve monotonicity)
    # become fully deterministic under harness load.  Rates are ns/byte of
    # filter input / compressor input respectively (zlib-6 streams at
    # ~60-70 MB/s on commodity cores -> ~15 ns/B; the filter's per-update
    # hash+version checks are ~2 ns/B).
    modeled_cpu: bool = False
    filter_cpu_ns_per_byte: float = 2.0
    compress_cpu_ns_per_byte: float = 15.0
    # how the streaming engine times the cross-epoch stream:
    # "incremental" (default) appends each epoch onto a StreamingTimeline
    # and simulates only the new events — O(E) total, byte-identical to the
    # full re-simulation by the bandwidth-admission finality argument;
    # "resim" keeps the O(E²) stitch-everything-and-rerun oracle
    # (repro.core.stream documents the identity argument; tests pin it).
    stream_mode: str = "incremental"
    # run-dataflow retention: keep_epochs=True (default) retains the full
    # per-epoch EpochStats list on RunStats.epochs (the historical surface);
    # keep_epochs=False caps RunStats.epochs at the trailing `stats_window`
    # epochs and the run-level totals come from the online RunSummary
    # instead (repro.core.sinks.RunAggregator) — byte-identical to the
    # retained path, memory O(window) instead of O(E).  A bounded run with
    # a serving plane needs ServeConfig(keep_epochs=False) too (rule table:
    # repro.analysis.config_check).
    keep_epochs: bool = True
    stats_window: int = 64
    # debug hook: statically verify every schedule the engine simulates
    # (repro.analysis.schedule_check.verify_schedule — acyclicity, phase
    # monotonicity along deps, clock-chain linearity, payload/node sanity)
    # before it runs.  O(V+E) per round; raises ScheduleVerificationError
    # on the first unsound DAG instead of silently mistiming it.
    verify_schedules: bool = False
    sync_strategy: str | None = None   # named wan_sync preset (overrides booleans)
    grouping: bool = True              # GeoCoCo hierarchical transmission
    filtering: bool = True             # white-data filter at aggregators
    tiv: bool = True                   # overlay relay exploitation
    tiv_margin: float = 0.05
    compression: bool = False          # zlib on WAN payloads (Fig 16)
    compression_level: int = 6
    schedule_name: str | None = None   # registered "schedule" builder
    filter_name: str | None = None     # registered "filter" implementation
    planner: str = "milp"              # registered "planner" strategy
    replan_threshold: float = 0.20
    replan_sustain: int = 3
    planner_time_limit_s: float = 10.0

    def __post_init__(self):
        # A named strategy drives the stage booleans (the shim direction);
        # nothing else is written back, so `dataclasses.replace` on the
        # booleans of a boolean-configured instance behaves as expected
        # (with sync_strategy set, the name wins on replace — by design;
        # ablate via the booleans or pass sync_strategy=None).
        # flag-compatibility constraints live in the declarative rule table
        # (repro.analysis.config_check) — one place for every flag, same
        # historical error messages
        validate_config(self)
        if self.sync_strategy is not None:
            spec = _strategies.get("wan_sync", self.sync_strategy)
            self.grouping = spec.grouping
            self.filtering = spec.filtering
            self.tiv = spec.tiv
            self.compression = spec.compression
        _strategies.get("planner", self.planner)      # fail fast on typos
        if self.schedule_name is not None:
            _strategies.get("schedule", self.schedule_name)
        if self.filter_name is not None:
            _strategies.get("filter", self.filter_name)

    @property
    def resolved_sync_strategy(self) -> str:
        if self.sync_strategy is not None:
            return self.sync_strategy
        return _strategies.wan_strategy_name(
            grouping=self.grouping, filtering=self.filtering,
            tiv=self.tiv, compression=self.compression,
        )

    @property
    def resolved_schedule_name(self) -> str:
        if self.schedule_name is not None:
            return self.schedule_name
        if self.sync_strategy is not None:
            return _strategies.get("wan_sync", self.sync_strategy).schedule
        return "hierarchical" if self.grouping else "all_to_all"

    @property
    def resolved_filter_name(self) -> str:
        if not self.filtering:
            return "none"
        if self.filter_name is not None:
            return self.filter_name
        if self.sync_strategy is not None:
            return _strategies.get("wan_sync", self.sync_strategy).filter
        return "whitedata"


@dataclasses.dataclass
class EpochStats:
    epoch: int
    n_txns: int
    committed: int
    aborted: int
    sync_ms: float                 # event engine: DAG critical path (CPU
    exec_ms: float                 # stages included where on the path);
    wall_ms: float                 # barrier engine: phase-sum makespan
    wan_bytes: float
    filter_stats: FilterStats | None
    filter_cpu_ms: float
    plan_method: str
    # critical-path vs overlapped split: sync_serial_ms is what a fully
    # serialized round would cost (barrier phase-sum + every group's
    # filter/compress CPU back-to-back), and sync_overlap_ms =
    # sync_serial_ms - sync_ms is the work the DAG hid — an exact identity
    # (no clamping: with bandwidth admission, event <= barrier + total CPU
    # is a theorem, so the overlap is never negative).  The barrier engine
    # doesn't model round CPU (pre-refactor semantics; see filter_cpu_ms),
    # so there serial == sync and overlap == 0 — the identity holds in
    # both engines.
    sync_serial_ms: float = 0.0
    sync_overlap_ms: float = 0.0
    # the honest split of sync_overlap_ms against the per-transfer compute
    # timeline: sync_cpu_hidden_ms is the filter/compress CPU that ran off
    # the critical path (hidden behind other groups' in-flight WAN traffic),
    # sync_wan_overlap_ms = sync_overlap_ms - sync_cpu_hidden_ms is pure
    # cross-stage WAN overlap (barrier waiting the DAG removed).  Before
    # this split, compute-dominated rounds reported filter-CPU savings as
    # "makespan slack" — the two are different resources.
    sync_cpu_hidden_ms: float = 0.0
    sync_wan_overlap_ms: float = 0.0
    # streaming engine only: wall_ms is the measured inter-commit gap in the
    # stitched multi-epoch simulation (stream_commit_ms is the absolute
    # commit time); pipeline_overlap_ms = max(epoch_ms, exec_ms, sync_ms) -
    # wall_ms is the wall-clock the cross-epoch pipeline saved vs the
    # formula model (negative for epochs paying off an inherited backlog).
    pipeline_overlap_ms: float = 0.0
    stream_commit_ms: float = 0.0
    # abort breakdown (validate_epoch_detailed): read_aborts failed the
    # read-validation rule (stale read versions — nonzero only under
    # staleness_feedback, where reads are versioned against per-node views),
    # ww_aborts lost a written key first-writer-wins.  The rules can overlap
    # (a txn may fail both), so read_aborts + ww_aborts >= aborted.
    read_aborts: int = 0
    ww_aborts: int = 0
    # staleness_feedback only: how many epochs each node's snapshot view
    # lagged the global state when this epoch's transactions executed
    # (mean/max over nodes; 0 = every replica executed against fresh state)
    view_lag_mean: float = 0.0
    view_lag_max: int = 0


@dataclasses.dataclass
class RunStats:
    """A run's report.  ``epochs`` is the retained per-epoch list — the full
    run under ``EngineConfig(keep_epochs=True)`` (the default), only the
    trailing ``stats_window`` under ``keep_epochs=False``.  The run-level
    totals below read ``summary`` (the :class:`~repro.core.sinks.RunSummary`
    the engine accumulated online, byte-identical to folding the full epochs
    list) when present and fall back to folding ``epochs`` when constructed
    directly without one.  ``makespans_ms`` / ``p99_sync_ms`` are inherently
    per-epoch arrays and always read ``epochs`` — under ``keep_epochs=False``
    they describe the retained window only (``summary.sync_ms_mean`` /
    ``.sync_ms_std`` / ``.sync_ms_max`` are the bounded-memory stand-ins).
    """

    epochs: list[EpochStats]
    msg_matrix: np.ndarray
    plan_time_s: float
    state_digest: str
    value_digest: str
    # the serving plane's report (EngineConfig(serve=...), streaming only);
    # None when the plane is off
    serve: ServeStats | None = None
    # online run-level totals (repro.core.sinks.RunSummary), set by
    # GeoCluster.run; None for hand-constructed instances
    summary: "RunSummary | None" = None

    @property
    def committed(self) -> int:
        if self.summary is not None:
            return self.summary.committed
        return sum(e.committed for e in self.epochs)

    @property
    def total_txns(self) -> int:
        if self.summary is not None:
            return self.summary.n_txns
        return sum(e.n_txns for e in self.epochs)

    @property
    def aborted(self) -> int:
        if self.summary is not None:
            return self.summary.aborted
        return sum(e.aborted for e in self.epochs)

    @property
    def read_aborts(self) -> int:
        """Transactions failing read-set validation (stale read versions)."""
        if self.summary is not None:
            return self.summary.read_aborts
        return sum(e.read_aborts for e in self.epochs)

    @property
    def ww_aborts(self) -> int:
        """Transactions losing a written key first-writer-wins."""
        if self.summary is not None:
            return self.summary.ww_aborts
        return sum(e.ww_aborts for e in self.epochs)

    @property
    def abort_rate(self) -> float:
        t = self.total_txns
        return self.aborted / t if t else 0.0

    @property
    def read_abort_rate(self) -> float:
        t = self.total_txns
        return self.read_aborts / t if t else 0.0

    @property
    def wall_s(self) -> float:
        if self.summary is not None:
            return self.summary.wall_ms / 1e3
        return sum(e.wall_ms for e in self.epochs) / 1e3

    @property
    def throughput_tps(self) -> float:
        w = self.wall_s
        return self.committed / w if w > 0 else 0.0

    @property
    def wan_bytes(self) -> float:
        if self.summary is not None:
            return self.summary.wan_bytes
        return sum(e.wan_bytes for e in self.epochs)

    @property
    def makespans_ms(self) -> np.ndarray:
        """Per-epoch DAG critical paths — of the *retained* epochs only
        (the trailing window under ``keep_epochs=False``)."""
        return np.array([e.sync_ms for e in self.epochs], dtype=float)

    @property
    def white_stats(self) -> FilterStats:
        if self.summary is not None:
            return self.summary.filter_stats
        out = FilterStats()
        for e in self.epochs:
            if e.filter_stats is not None:
                out = out.merge(e.filter_stats)
        return out

    @property
    def p99_sync_ms(self) -> float:
        """p99 of :attr:`makespans_ms` — window-limited under
        ``keep_epochs=False``; use ``summary.sync_ms_max`` for a bounded-
        memory whole-run bound."""
        ms = self.makespans_ms
        if ms.size == 0:
            return 0.0
        return float(np.percentile(ms, 99))

    @property
    def overlap_ms(self) -> float:
        """Total CPU/WAN work hidden by the pipelined transmission DAG."""
        if self.summary is not None:
            return self.summary.sync_overlap_ms
        return sum(e.sync_overlap_ms for e in self.epochs)

    @property
    def pipeline_overlap_ms(self) -> float:
        """Total wall-clock the streaming cross-epoch pipeline saved vs the
        ``max(epoch, exec, sync)`` formula (0.0 for non-streaming runs)."""
        if self.summary is not None:
            return self.summary.pipeline_overlap_ms
        return sum(e.pipeline_overlap_ms for e in self.epochs)


@dataclasses.dataclass
class _EpochRound:
    """The timing-independent product of one epoch: the schedule to time,
    the commit outcome, and the planning/filtering context the stats need.
    (The epoch's latency matrix is *not* here — it is always
    ``trace[epoch % len(trace)]``, and retaining a copy per round held E
    duplicated matrices alive; see :class:`~repro.core.simulator.
    EpochLatencyCycle`.)"""

    epoch: int
    schedule: TransmissionSchedule
    n_txns: int
    committed: int
    aborted: int
    read_aborts: int
    ww_aborts: int
    ups: list[Update]
    exec_ms: float
    node_exec_ms: np.ndarray
    filter_cpu_ms: float
    fstats: FilterStats | None
    plan_method: str
    modeled_cpu_ms: float


def _compressed_size(updates: Sequence[Update], level: int) -> int:
    blob = b"".join(u.key.encode() + u.value for u in updates)
    if not blob:
        return 0
    return len(_zlib.compress(blob, level)) + 24 * len(updates)


def _batch_bytes(updates: Sequence[Update]) -> int:
    return sum(u.nbytes for u in updates)


def advance_views(
    n_nodes: int,
    views: list[DeltaCRDTStore],
    view_next: np.ndarray,
    pending_ups: dict[int, list[Update]],
    commit_at: Callable[[int, int], float],
    n_done: int,
    now_ms: float,
) -> None:
    """Merge every epoch the stitched simulation has delivered to each
    node by ``now_ms`` into that node's snapshot view.  Views advance a
    contiguous epoch prefix (a node merges epoch k only once its k-th
    inbound transfers have all delivered — the same per-node commit
    dependency ``stitch_schedules`` gates sends on).

    ``commit_at(k, i)`` reads the measured commit time of epoch ``k`` at
    node ``i`` for ``k < n_done`` (a point read so the caller may store
    the matrix in an evicting window); ``pending_ups`` maps epoch ->
    committed updates and is the *retention frontier's* backing store —
    entries every view has merged past (``< view_next.min()``) are
    released here, because no view will ever request them again.

    This is the frontier logic the eviction-safety theorem is about, so it
    lives at module level where both the engine (``GeoCluster``) and the
    model checker (:mod:`repro.analysis.modelcheck`) drive the *same*
    code."""
    for i in range(n_nodes):
        nxt = int(view_next[i])
        while nxt < n_done and commit_at(nxt, i) <= now_ms + 1e-9:
            views[i].apply_many(pending_ups[nxt])
            nxt += 1
        view_next[i] = nxt
    floor = int(view_next.min()) if len(view_next) else 0
    for k in [k for k in pending_ups if k < floor]:
        del pending_ups[k]


class _Timing:
    """Where an epoch's commit times come from; :meth:`GeoCluster.run` asks
    this once per run.  ``append(rnd, lat)`` takes a simulated round and
    returns the marks of the epochs whose commit times are now final, in
    epoch order; ``finish()`` returns the rest.  A mark is ``None`` (no
    stream: the formula times the epoch) or ``(absolute commit ms,
    cumulative per-node commit row)``.  ``commit_at(k, i)`` and ``n_done``
    feed the feedback loop's :func:`advance_views`; ``evict(before)``
    releases commit rows below the views' merge frontier.

    This base is the isolated source of the barrier and event engines: no
    cross-epoch stream, so an epoch is final as soon as its round is."""

    n_done = 0

    def append(self, rnd: _EpochRound, lat: np.ndarray) -> list:
        return [None]

    def finish(self) -> list:
        return []

    def evict(self, before: int) -> None:
        pass


class _TimelineTiming(_Timing):
    """``stream_mode="incremental"``: each round is appended onto a
    :class:`~repro.core.stream.StreamingTimeline`, which simulates only its
    events.  With bandwidth admission later arrivals never move an earlier
    epoch, so its times are final the moment the append returns: O(E) time,
    and memory bounded by the slowest view (:meth:`evict`)."""

    def __init__(self, cluster: "GeoCluster"):
        cfg = cluster.cfg
        self._timeline = StreamingTimeline(
            cfg.n_nodes, bandwidth_mbps=cluster.bandwidth, loss=cluster.loss,
            epoch_ms=cfg.epoch_ms, verify=cfg.verify_schedules,
        )
        self.commit_at = self._timeline.commit_at

    def append(self, rnd: _EpochRound, lat: np.ndarray) -> list:
        et = self._timeline.append_epoch(rnd.schedule, lat,
                                         node_exec_ms=rnd.node_exec_ms)
        self.n_done = self._timeline.n_epochs
        return [(et.finish_max_ms, et.commit_ms)]

    def evict(self, before: int) -> None:
        self._timeline.evict_commit_rows(before)


class _ResimTiming(_Timing):
    """``stream_mode="resim"``, the O(E²) reference oracle: it keeps every
    round, and stitches and re-simulates the whole prefix — after each
    append under ``staleness_feedback`` (the views need each epoch's
    times), once at :meth:`finish` otherwise."""

    def __init__(self, cluster: "GeoCluster", lats: EpochLatencyCycle):
        self._cluster = cluster
        self._lats = lats
        self._feedback = cluster.cfg.staleness_feedback
        self._schedules: list[TransmissionSchedule] = []
        self._exec: list[np.ndarray] = []
        self._commit = np.zeros((0, cluster.cfg.n_nodes))

    def commit_at(self, k: int, i: int) -> float:
        return float(self._commit[k, i])

    def append(self, rnd: _EpochRound, lat: np.ndarray) -> list:
        self._schedules.append(rnd.schedule)
        self._exec.append(rnd.node_exec_ms)
        if not self._feedback:
            return []
        return self._stream()[-1:]

    def finish(self) -> list:
        if self._feedback or not self._schedules:
            return []
        return self._stream()

    def _stream(self) -> list:
        """Stitch every round so far, run one event simulation over them
        and return every epoch's mark."""
        c, cfg = self._cluster, self._cluster.cfg
        stitched = stitch_schedules(self._schedules, node_exec_ms=self._exec,
                                    epoch_ms=cfg.epoch_ms, n=cfg.n_nodes)
        sim = WANSimulator(self._lats[0], c.bandwidth, loss=c.loss,
                           rng=c.rng, verify=cfg.verify_schedules)
        stream = sim.run(stitched, lats=self._lats)
        n_epochs = len(self._schedules)
        self._commit = node_commit_ms(stitched, stream, cfg.n_nodes, n_epochs)
        self.n_done = n_epochs
        # per-epoch absolute commit marks in one grouped pass
        epoch_of = np.array([t.epoch for t in stitched.transfers])
        marks = np.full(n_epochs, -np.inf)
        np.maximum.at(marks, epoch_of, stream.finish_ms)
        return list(zip(marks.tolist(), self._commit))


class GeoCluster:
    """Full-replica multi-master cluster over a simulated WAN."""

    def __init__(
        self,
        cfg: EngineConfig,
        *,
        control=None,
        bandwidth_mbps: np.ndarray | float = np.inf,
        loss: np.ndarray | float = 0.0,
        wan_mask: np.ndarray | None = None,
        seed: int = 0,
    ):
        """``wan_mask`` (bool n x n): which links are WAN; when given,
        per-epoch ``wan_bytes`` counts only those links — matching the
        paper's NIC-level inter-region egress measurement (Sec 6.1).  Cheap
        intra-region LAN traffic (the gather/scatter phases) is excluded,
        exactly as in the paper's bandwidth-utilization methodology.

        ``control`` is a ``repro.control.ControlPlane``; the engine no
        longer constructs a private Replanner — it pushes each epoch's
        latency matrix through the plane and takes the (damped) plan back,
        so every other subscriber (e.g. a device-plane Trainer sharing the
        instance) observes the same ``PlanChanged`` events.  When omitted,
        the engine builds its own plane from the config's replan
        parameters."""
        self.cfg = cfg
        self.bandwidth = bandwidth_mbps
        self.loss = loss
        self.wan_mask = wan_mask
        self.store = DeltaCRDTStore()  # replicated state (identical on all nodes)
        self.rng = np.random.default_rng(seed)
        # strategy resolution happens once, through the two-plane registry:
        # the engine never hard-codes a builder or filter implementation
        self._schedule_fn = _strategies.get("schedule", cfg.resolved_schedule_name)
        self._flat_schedule_fn = _strategies.get("schedule", "all_to_all")
        self._filter_fn = _strategies.get("filter", cfg.resolved_filter_name)
        # registry-dependent contract rules (grouping-engine builder
        # signature, flat engine runs all_to_all by definition) — fail
        # fast at attach, not mid-run; the rules themselves live in the
        # declarative config_check table
        validate_config(cfg, stage="cluster")
        self._schedule_takes_compute = False
        if cfg.grouping:
            # pipelined engine: builders that accept group_compute_ms get the
            # per-group filter/compress CPU charged on their exchange edges
            import inspect

            params = inspect.signature(self._schedule_fn).parameters
            self._schedule_takes_compute = "group_compute_ms" in params
        self.plan_time_s = 0.0
        self._payload_ewma = 0.0   # observed per-node epoch payload (bytes)
        self._keep_ewma = 1.0      # observed post-filter keep ratio
        self.control = self._wire_control(control)
        self.msg_matrix = np.zeros((cfg.n_nodes, cfg.n_nodes), dtype=int)

    def _wire_control(self, control):
        """Attach to (or build) the network control plane.

        The engine contributes its bandwidth/payload-aware plan ranking to
        the plane — but only when no better-informed planner is already
        bound (``bind_planner`` keeps the first non-default planner on a
        shared instance)."""
        from ..control.plane import ControlPlane

        cfg = self.cfg
        if control is None:
            control = ControlPlane(
                replan_threshold=cfg.replan_threshold,
                replan_sustain=cfg.replan_sustain,
                tiv=cfg.tiv,
                tiv_margin=cfg.tiv_margin,
            )
        control.bind_planner(self._plan_fn)
        return control

    def _plan_fn(self, lat: np.ndarray) -> GroupPlan:
        """Bandwidth/payload-aware plan ranking (Sec 4.1 "balance latency
        and resource utilization"), fed by per-epoch payload observations."""
        from .planner import best_plan

        cfg = self.cfg
        t0 = time.perf_counter()
        plan = best_plan(
            lat,
            tiv=cfg.tiv,
            tiv_margin=cfg.tiv_margin,
            method=cfg.planner,
            time_limit_s=cfg.planner_time_limit_s,
            payload_bytes=self._payload_ewma or None,
            bandwidth_mbps=self.bandwidth,
            filter_keep=self._keep_ewma if cfg.filtering else 1.0,
            barrier=cfg.barrier,  # rank plans by the makespan we will execute
            streaming=cfg.streaming,  # ... incl. cross-epoch pipelining
        )
        self.plan_time_s += time.perf_counter() - t0
        return plan

    # -- one epoch -------------------------------------------------------------

    def _prepare_epoch(
        self,
        epoch: int,
        txns_by_node: dict[int, list[Txn]],
        lat: np.ndarray,
        views: Sequence[DeltaCRDTStore] | None = None,
    ) -> "_EpochRound":
        """Everything timing-independent about one epoch: planning, filtering,
        schedule construction, deterministic validation and the CRDT commit.
        The simulator never touches the store, so commit content is identical
        whichever engine (barrier / event / streaming) later times the round.

        ``views`` (staleness_feedback only) are the per-node snapshot views;
        when given, each group's aggregator filters against *its own* view
        instead of the globally-merged store — a backlogged aggregator holds
        smaller versions, so its stale/null-effect rules fire less and filter
        efficacy degrades with network conditions (the rules stay sound: a
        version stale against an older snapshot is stale against any newer
        one).  Validation always runs against the globally-merged snapshot —
        every replica holds the full epoch's metadata by commit time.
        """
        cfg = self.cfg
        n = cfg.n_nodes
        snapshot = self.store  # epoch-start replicated snapshot

        all_txns = [t for ts in txns_by_node.values() for t in ts]
        n_txns = len(all_txns)
        node_exec_ms = np.array(
            [len(txns_by_node.get(i, [])) * cfg.txn_exec_us / 1e3
             for i in range(n)],
            dtype=float,
        )
        exec_ms = float(node_exec_ms.max()) if n else 0.0

        filter_cpu_ms = 0.0
        fstats: FilterStats | None = None

        if cfg.grouping:
            node_payload = np.zeros(n)
            for node, ts in txns_by_node.items():
                node_payload[node] = sum(
                    u.nbytes for t in ts for u in txn_updates(t)
                )
            # the bandwidth-aware planner needs the payload estimate *before*
            # the (damped) plan request, or the first latency-only plan
            # would persist until a latency deviation
            mean_payload = float(np.mean(node_payload)) if n else 0.0
            self._payload_ewma = (
                0.7 * self._payload_ewma + 0.3 * mean_payload
                if self._payload_ewma
                else mean_payload
            )
            plan = self.control.observe(lat)
            # Validation metadata (read/write sets) always flows globally, as
            # in GeoGauss; filtering strips white-data *payloads* only.  The
            # commit outcome is therefore bit-identical to the baseline.
            surviving = all_txns
            group_payload = np.zeros(plan.k)
            # per-group aggregator CPU (filter + compression) — the pipelined
            # DAG charges it on that group's exchange transfers so it overlaps
            # other groups' in-flight WAN traffic
            group_cpu_ms = np.zeros(plan.k)
            fstats = FilterStats()
            for j, (group, agg) in enumerate(zip(plan.groups, plan.aggregators)):
                gtxns = [t for i in group for t in txns_by_node.get(i, [])]
                # the aggregator filters against the state *it* holds: its
                # own (possibly stale) view under staleness_feedback, the
                # globally-merged store otherwise
                fsnap = snapshot if views is None else views[agg]
                t0 = time.perf_counter()
                fr = self._filter_fn(gtxns, fsnap)
                if cfg.filtering:
                    # the no_filter passthrough's byte accounting is not a
                    # filtering cost — keep the baseline's filter CPU at 0
                    if cfg.modeled_cpu:
                        dt_ms = (
                            fr.stats.total_bytes
                            * cfg.filter_cpu_ns_per_byte / 1e6
                        )
                    else:
                        dt_ms = (time.perf_counter() - t0) * 1e3
                    filter_cpu_ms += dt_ms
                    group_cpu_ms[j] += dt_ms
                fstats = fstats.merge(fr.stats)
                dropped = fr.stats.total_updates - fr.stats.kept_updates
                if cfg.compression:
                    t0 = time.perf_counter()
                    group_payload[j] = _compressed_size(
                        fr.kept, cfg.compression_level
                    ) + 24 * dropped
                    if cfg.modeled_cpu:
                        group_cpu_ms[j] += (
                            sum(u.nbytes for u in fr.kept)
                            * cfg.compress_cpu_ns_per_byte / 1e6
                        )
                    else:
                        group_cpu_ms[j] += (time.perf_counter() - t0) * 1e3
                else:
                    group_payload[j] = fr.stats.wire_bytes
            if cfg.compression:
                node_payload = np.array(
                    [
                        _compressed_size(
                            [u for t in txns_by_node.get(i, []) for u in txn_updates(t)],
                            cfg.compression_level,
                        )
                        for i in range(n)
                    ],
                    dtype=float,
                )
            sched_kw = {}
            modeled_cpu_ms = 0.0
            if self._schedule_takes_compute and not cfg.barrier:
                sched_kw["group_compute_ms"] = group_cpu_ms
                # only CPU the DAG actually charges may count as "hidden"
                # in the serialized reference below
                modeled_cpu_ms = float(group_cpu_ms.sum())
            schedule = self._schedule_fn(
                plan,
                node_payload,
                group_payload_bytes=group_payload,
                lat=lat,
                tiv=cfg.tiv,
                tiv_margin=cfg.tiv_margin,
                **sched_kw,
            )
            plan_method = plan.method
        else:
            surviving = all_txns
            payload = np.array(
                [
                    (
                        _compressed_size(
                            [u for t in txns_by_node.get(i, []) for u in txn_updates(t)],
                            cfg.compression_level,
                        )
                        if cfg.compression
                        else sum(
                            u.nbytes
                            for t in txns_by_node.get(i, [])
                            for u in txn_updates(t)
                        )
                    )
                    for i in range(n)
                ],
                dtype=float,
            )
            schedule = self._flat_schedule_fn(n, payload)
            plan_method = "none"
            modeled_cpu_ms = 0.0

        # feed filter observations to the bandwidth-aware planner
        if cfg.grouping and cfg.filtering and fstats is not None and fstats.total_bytes:
            keep = fstats.wire_bytes / fstats.total_bytes
            self._keep_ewma = 0.7 * self._keep_ewma + 0.3 * keep

        # deterministic global validation over surviving txns, then CRDT
        # merge.  Epoch commit sinks the *full* DAG (every transfer
        # delivered) — the engines change when bytes move, never which
        # bytes commit, so this is timing-independent.  Validation always
        # runs against the globally-merged epoch-start snapshot (every
        # replica holds the full epoch's write/read metadata by commit
        # time); under staleness_feedback the *read versions* inside the
        # transactions came from per-node views, which is what arms the
        # read rule.
        vres = validate_epoch_detailed(surviving, snapshot)
        ups = [
            u for t in surviving if t.txn_id in vres.committed
            for u in txn_updates(t)
        ]
        pre_aborted = n_txns - len(surviving)
        committed = len(vres.committed)
        self.store.apply_many(ups)

        return _EpochRound(
            epoch=epoch,
            schedule=schedule,
            n_txns=n_txns,
            committed=committed,
            aborted=pre_aborted + len(vres.aborted),
            read_aborts=len(vres.read_aborted),
            ww_aborts=len(vres.ww_aborted),
            ups=ups,
            exec_ms=exec_ms,
            node_exec_ms=node_exec_ms,
            filter_cpu_ms=filter_cpu_ms,
            fstats=fstats,
            plan_method=plan_method,
            modeled_cpu_ms=modeled_cpu_ms,
        )

    def _epoch_stats(
        self,
        rnd: "_EpochRound",
        sim: WANSimulator,
        res,
        mark: tuple[float, np.ndarray] | None = None,
        prev_commit_ms: float = 0.0,
        lag: tuple[float, int] = (0.0, 0),
    ) -> EpochStats:
        """Assemble one epoch's stats from its (isolated) round simulation.
        ``mark`` is the timing source's (absolute commit, commit row) of the
        epoch in the cross-epoch stream; without a stream (None) the
        wall-clock is the formula ``max(epoch_ms, exec_ms, sync_ms)``.
        ``lag`` is the views' (mean, max) lag in epochs when the epoch
        executed."""
        cfg = self.cfg
        schedule = rnd.schedule
        if cfg.barrier:
            # the barrier engine doesn't model CPU inside the round at all
            # (pre-refactor semantics; filter_cpu_ms reports it separately),
            # so serial == sync and nothing is hidden
            sync_serial_ms = res.makespan_ms
            sync_overlap_ms = 0.0
            cpu_hidden_ms = 0.0
            wan_overlap_ms = 0.0
        else:
            # serialized reference: barrier phase-sum + back-to-back CPU
            # (only the CPU the DAG modeled — phase-sum only, no second
            # full simulation).  The identity serial == sync + overlap is
            # exact: with bandwidth admission, event <= barrier + total CPU
            # is a theorem, so no clamping is needed.
            sync_serial_ms = sim.barrier_makespan_ms(schedule) + rnd.modeled_cpu_ms
            sync_overlap_ms = sync_serial_ms - res.makespan_ms
            # honest CPU/WAN split against the per-transfer timeline: CPU
            # "on the path" is compute that actually gated a critical-path
            # transfer's wire start (the gap between its dependencies
            # sinking and the wire), everything else was hidden behind
            # other groups' in-flight transfers
            cpu_on_path_ms = 0.0
            for i in res.critical_path:
                t = schedule.transfers[i]
                if t.compute_ms <= 0.0:
                    continue
                ready = max((float(res.finish_ms[d]) for d in t.deps),
                            default=0.0)
                gap = max(float(res.start_ms[i]) - ready, 0.0)
                cpu_on_path_ms += min(t.compute_ms, gap)
            cpu_hidden_ms = max(rnd.modeled_cpu_ms - cpu_on_path_ms, 0.0)
            wan_overlap_ms = sync_overlap_ms - cpu_hidden_ms
        if self.wan_mask is not None:
            wan_bytes = float((res.link_bytes * self.wan_mask).sum())
        else:
            wan_bytes = res.total_bytes
        formula_ms = max(cfg.epoch_ms, rnd.exec_ms, res.makespan_ms)
        commit_ms = 0.0 if mark is None else mark[0]
        wall_ms = formula_ms if mark is None else commit_ms - prev_commit_ms
        return EpochStats(
            epoch=rnd.epoch,
            n_txns=rnd.n_txns,
            committed=rnd.committed,
            aborted=rnd.aborted,
            sync_ms=res.makespan_ms,
            exec_ms=rnd.exec_ms,
            wall_ms=wall_ms,
            wan_bytes=wan_bytes,
            filter_stats=rnd.fstats,
            filter_cpu_ms=rnd.filter_cpu_ms,
            plan_method=rnd.plan_method,
            sync_serial_ms=sync_serial_ms,
            sync_overlap_ms=sync_overlap_ms,
            sync_cpu_hidden_ms=cpu_hidden_ms,
            sync_wan_overlap_ms=wan_overlap_ms,
            pipeline_overlap_ms=formula_ms - wall_ms,
            stream_commit_ms=commit_ms,
            read_aborts=rnd.read_aborts,
            ww_aborts=rnd.ww_aborts,
            view_lag_mean=lag[0],
            view_lag_max=lag[1],
        )

    # -- the epoch loop --------------------------------------------------------

    def _timing_source(self, lats: EpochLatencyCycle) -> _Timing:
        """The one place the engine's commit timing is chosen."""
        if not self.cfg.streaming:
            return _Timing()
        if self.cfg.stream_mode == "incremental":
            return _TimelineTiming(self)
        return _ResimTiming(self, lats)

    def run(self, generator, trace, *, txns_per_node: int = 20,
            n_epochs: int | None = None) -> RunStats:
        """Run ``n_epochs`` epochs (default ``len(trace)``); epoch ``e`` sees
        ``trace[e % len(trace)]``.

        One loop serves every engine.  Each epoch it advances the per-node
        snapshot views (``staleness_feedback`` only), draws the epoch's
        transactions against them (else against the replicated store),
        prepares the timing-independent round (:meth:`_prepare_epoch`),
        simulates it in isolation (the reference for ``sync_ms``, the
        serial/overlap split and the byte accounting) and hands it to the
        timing source.  Epochs whose commit times are then final go, in
        epoch order, to the sinks: the run aggregator and, with ``serve``,
        the serving plane's :class:`~repro.serve.plane.ServingSink`.

        The timing source (:meth:`_timing_source`) is the only difference
        between the engines: none for the barrier and event engines (the
        wall-clock is ``max(epoch_ms, exec_ms, sync_ms)``), an appendable
        :class:`~repro.core.stream.StreamingTimeline` for
        ``stream_mode="incremental"``, and the O(E²) re-simulation oracle
        for ``"resim"``; the two streams' times are byte-identical.  Commit
        content never depends on the timing, so without feedback all four
        engines' digests agree.

        With ``staleness_feedback=True`` the transactions of epoch ``e``
        execute optimistically at ``e * epoch_ms`` against the executing
        node's view, which holds only the epochs the stream has delivered
        to that node by then, so read-validation aborts become a function
        of network conditions.  (Sends stay gated on the node's
        previous-epoch commit, as in the stitched DAG.)
        """
        cfg = self.cfg
        n = cfg.n_nodes
        feedback = cfg.staleness_feedback
        n_epochs = n_epochs if n_epochs is not None else len(trace)
        lats = EpochLatencyCycle(trace, max(n_epochs, 1))
        timing = self._timing_source(lats)
        agg = RunAggregator(keep_epochs=cfg.keep_epochs,
                            window=cfg.stats_window)
        sinks: list[EpochSink] = [agg]
        serve_sink = None
        if cfg.serve is not None:
            from ..serve.plane import ServingSink

            serve_sink = ServingSink(cfg.serve, n, cfg.epoch_ms)
            sinks.append(serve_sink)
        views = view_next = None
        pending_ups: dict[int, list[Update]] = {}  # epoch -> unmerged updates
        if feedback:
            views = [DeltaCRDTStore(i) for i in range(n)]
            view_next = np.zeros(n, dtype=int)
        # simulated rounds whose commit times are not final yet
        pending: collections.deque = collections.deque()
        last_commit = 0.0

        def release(marks) -> None:
            nonlocal last_commit
            for mark in marks:
                rnd, sim, res, lag = pending.popleft()
                stats = self._epoch_stats(rnd, sim, res, mark, last_commit, lag)
                ctx = None
                if mark is not None:
                    last_commit, row = mark
                    ctx = EpochContext(epoch=rnd.epoch, commit_row=row,
                                       lat=lats[rnd.epoch])
                for s in sinks:
                    s.on_epoch(stats, ctx)

        for e in range(n_epochs):
            lat = lats[e]
            lag = (0.0, 0)
            snapshot = self.store
            if feedback:
                advance_views(n, views, view_next, pending_ups,
                              timing.commit_at, timing.n_done,
                              e * cfg.epoch_ms)
                behind = e - view_next
                lag = (float(behind.mean()), int(behind.max()))
                snapshot = views
            txns = generator.epoch_txns(e, txns_per_node, snapshot=snapshot)
            rnd = self._prepare_epoch(e, txns, lat, views=views)
            sim = WANSimulator(lat, self.bandwidth, loss=self.loss,
                               rng=self.rng, barrier=cfg.barrier,
                               verify=cfg.verify_schedules)
            res = sim.run(rnd.schedule)
            self.msg_matrix += res.msg_matrix
            pending.append((rnd, sim, res, lag))
            release(timing.append(rnd, lat))
            if feedback:
                pending_ups[e] = rnd.ups
            # commit rows below the slowest view's merge frontier are never
            # read again (advance_views only reads forward of view_next);
            # without feedback nothing reads them after the sinks have
            timing.evict(int(view_next.min()) if feedback else e + 1)
        release(timing.finish())
        serve_stats = None
        if serve_sink is not None and n_epochs:
            # wall_ms covers the full client window even when the last
            # commit lands inside it
            serve_stats = serve_sink.finish(
                wall_ms=max(last_commit, n_epochs * cfg.epoch_ms)
            )
        return RunStats(
            epochs=agg.epochs,
            msg_matrix=self.msg_matrix.copy(),
            plan_time_s=self.plan_time_s,
            state_digest=self.store.digest(),
            value_digest=self.store.digest(values_only=True),
            serve=serve_stats,
            summary=agg.summary,
        )


# ---------------------------------------------------------------------------
# Raft / CockroachDB plane (Sec 5 "Extensions", Fig 11b)
# ---------------------------------------------------------------------------


class RaftCluster:
    """Leader-based replication with optional GeoCoCo relay of AppendEntries.

    Ranges are hashed to leaders; a write batch commits once a majority of
    replicas ack.  GeoCoCo hooks RaftTransport: the leader sends one copy per
    group to the aggregator, which relays to members; acks travel back the
    same path.  Quorum semantics are unchanged (the paper's non-intrusive
    integration).

    Commit latency runs the replication fan-out through the **event-driven
    simulator** (``leader_schedule`` -> per-follower delivery times + ack
    propagation back): with constrained bandwidth the leader's NIC
    serializes its appends, so the quorum time reflects contention — the
    closed-form hop sums (kept as a private reference) charge every hop an
    uncontended wire and agree with the event engine exactly on
    contention-free (infinite-bandwidth) matrices.  Results are memoized
    per ``(latency matrix, leader, payload)`` — one epoch's batches all see
    the same network, so per-txn recomputation was pure waste (the plan
    search is also cached per matrix).
    """

    def __init__(
        self,
        n_nodes: int,
        *,
        grouping: bool = True,
        tiv: bool = True,
        planner: str = "kcenter",
        bandwidth_mbps: np.ndarray | float = np.inf,
        loss: np.ndarray | float = 0.0,
        seed: int = 0,
    ):
        self.n = n_nodes
        self.grouping = grouping
        self.tiv = tiv
        self.planner = planner
        self.bandwidth = bandwidth_mbps
        self.loss = loss
        self.rng = np.random.default_rng(seed)
        self._commit_cache: dict[tuple, float] = {}
        self._plan_cache: dict[bytes, "GroupPlan"] = {}
        self.commit_cache_hits = 0

    # -- quorum helpers --------------------------------------------------------

    def _ack_ms(self, lat: np.ndarray) -> np.ndarray:
        """Per-node ack-return latency to the leader's column: TIV-effective
        on the grouped (overlay) path, direct otherwise — matching the
        deployment (Sec 5 deploys relays on the grouped WAN paths)."""
        from .latency import one_relay_effective

        if self.grouping and self.tiv:
            eff, _ = one_relay_effective(lat, margin=0.05)
            return eff
        return lat

    def _plan(self, lat: np.ndarray, key: bytes) -> "GroupPlan":
        plan = self._plan_cache.get(key)
        if plan is None:
            from .planner import best_plan

            plan = best_plan(lat, tiv=self.tiv, method=self.planner)
            self._plan_cache[key] = plan
        return plan

    def _quorum_ms(self, res, transfers, leader: int, ack: np.ndarray,
                   epoch: int | None = None) -> float:
        """Majority-quorum commit time from an event-engine result: each
        follower's delivery plus its ack back to the leader, quorum-th
        smallest (leader + quorum followers = majority).  ``epoch``
        restricts to one batch of a stitched multi-batch stream."""
        times = [
            float(res.finish_ms[i]) + float(ack[t.dst, leader])
            for i, t in enumerate(transfers)
            if t.dst != leader and t.src != t.dst
            and (epoch is None or t.epoch == epoch)
        ]
        times.sort()
        quorum = self.n // 2
        return float(times[quorum - 1]) if quorum >= 1 else 0.0

    def commit_latency_ms(
        self, lat: np.ndarray, leader: int, payload_bytes: float
    ) -> float:
        """Latency for one replicated batch to reach majority quorum,
        measured by the event engine (memoized per matrix/leader/payload)."""
        lat = np.asarray(lat, dtype=float)
        mat_key = lat.tobytes()
        key = (mat_key, int(leader), float(payload_bytes))
        hit = self._commit_cache.get(key)
        if hit is not None:
            self.commit_cache_hits += 1
            return hit
        sim = WANSimulator(lat, self.bandwidth, loss=self.loss, rng=self.rng)
        plan = self._plan(lat, mat_key) if self.grouping else None
        sched = leader_schedule(self.n, leader, payload_bytes, plan)
        res = sim.run(sched)
        val = self._quorum_ms(res, sched.transfers, leader, self._ack_ms(lat))
        self._commit_cache[key] = val
        return val

    def _closed_form_commit_latency_ms(
        self, lat: np.ndarray, leader: int, payload_bytes: float
    ) -> float:
        """The pre-event-engine hop-sum model, kept as the contention-free
        reference: every hop pays propagation + an *uncontended* wire, so it
        matches the event engine exactly when bandwidth is infinite (and
        undercounts the leader's NIC serialization otherwise).  Mirrors
        ``leader_schedule``'s paths: the leader relays directly to its own
        group's members."""
        n = self.n
        sim = WANSimulator(lat, self.bandwidth, loss=self.loss, rng=self.rng)
        ack = self._ack_ms(lat)
        times = []
        if not self.grouping:
            for f in range(n):
                if f != leader:
                    times.append(
                        sim._hop_time(leader, f, payload_bytes)
                        + ack[f, leader]
                    )
        else:
            plan = self._plan(np.asarray(lat, dtype=float),
                              np.asarray(lat, dtype=float).tobytes())
            for g, a in zip(plan.groups, plan.aggregators):
                tgt = a if leader not in g else leader
                first = (
                    sim._hop_time(leader, tgt, payload_bytes)
                    if tgt != leader else 0.0
                )
                for f in g:
                    if f == leader:
                        continue
                    hop = 0.0 if f == tgt else sim._hop_time(tgt, f, payload_bytes)
                    times.append(first + hop + ack[f, leader])
        times.sort()
        quorum = n // 2
        return float(times[quorum - 1]) if quorum >= 1 else 0.0

    def pipelined_commit_ms(
        self, lat: np.ndarray, leader: int, payload_bytes: float,
        batches: int,
    ) -> float:
        """Commit time of the *last* of ``batches`` replication batches
        pipelined through one stitched leader-schedule stream.

        The batches share one event simulation
        (:func:`~repro.core.schedule.stitch_schedules` chains the per-batch
        leader DAGs; bandwidth admission serializes same-NIC appends in
        batch order), so in-flight batches contend for the leader's NIC
        instead of replicating for free.  On contention-free
        (infinite-bandwidth) matrices every batch streams at propagation
        speed and the last batch commits exactly when a single batch would
        — recovering the historical independent-batch model.  Memoized per
        ``(matrix, leader, payload, batches)``.
        """
        if batches <= 1:
            return self.commit_latency_ms(lat, leader, payload_bytes)
        lat = np.asarray(lat, dtype=float)
        mat_key = lat.tobytes()
        key = (mat_key, int(leader), float(payload_bytes), int(batches))
        hit = self._commit_cache.get(key)
        if hit is not None:
            self.commit_cache_hits += 1
            return hit
        plan = self._plan(lat, mat_key) if self.grouping else None
        one = leader_schedule(self.n, leader, payload_bytes, plan)
        # incremental timeline: only the last batch's segment matters for
        # the quorum, and appending is O(batch) instead of re-simulating
        # the whole stitched stream (byte-identical — see repro.core.stream;
        # _pipelined_commit_ms_resim is the tested oracle)
        timeline = StreamingTimeline(self.n, bandwidth_mbps=self.bandwidth,
                                     loss=self.loss)
        for _ in range(batches):
            et = timeline.append_epoch(one, lat)
        val = self._quorum_ms(et, et.transfers, leader,
                              self._ack_ms(lat), epoch=batches - 1)
        self._commit_cache[key] = val
        return val

    def _pipelined_commit_ms_resim(
        self, lat: np.ndarray, leader: int, payload_bytes: float,
        batches: int,
    ) -> float:
        """O(batches²) reference oracle for :meth:`pipelined_commit_ms`:
        stitch every batch and re-run the full event simulation.  Kept
        uncached for the incremental-identity regression tests."""
        lat = np.asarray(lat, dtype=float)
        sim = WANSimulator(lat, self.bandwidth, loss=self.loss, rng=self.rng)
        plan = self._plan(lat, lat.tobytes()) if self.grouping else None
        one = leader_schedule(self.n, leader, payload_bytes, plan)
        stitched = stitch_schedules([one] * batches, n=self.n)
        res = sim.run(stitched)
        return self._quorum_ms(res, stitched.transfers, leader,
                               self._ack_ms(lat), epoch=batches - 1)

    def throughput(
        self,
        trace,
        *,
        payload_bytes: float = 64_000.0,
        batches_in_flight: int = 8,
        ops_per_batch: int = 100,
    ) -> float:
        """Modeled ops/s: ``batches_in_flight`` batches pipelined through
        one stitched leader-schedule stream per trace step.

        The window closes when the last in-flight batch reaches quorum, so
        ops/s = ops * batches / mean(last-batch commit).  The historical
        model multiplied a *single* batch's mean commit latency by
        ``batches_in_flight`` — linear scaling that ignored the leader's
        NIC: on finite-bandwidth matrices it overstated throughput by up to
        the full pipelining factor.  The stitched stream reduces to it
        exactly at ``batches_in_flight=1`` and on infinite-bandwidth
        matrices (no contention to model).
        """
        last = []
        for lat in trace:
            leader = int(self.rng.integers(0, self.n))
            last.append(self.pipelined_commit_ms(
                lat, leader, payload_bytes, batches_in_flight))
        if not last:
            return 0.0
        mean_last = float(np.mean(last))
        if mean_last <= 0.0:
            return 0.0
        return ops_per_batch * batches_in_flight / (mean_last / 1e3)
