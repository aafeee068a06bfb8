"""Long-horizon streaming: O(E) incremental timeline + vectorized OCC.

Before this module's tentpole, every epoch of a streaming feedback run
re-stitched and re-simulated the entire prefix (now the ``resim`` oracle), making
an E-epoch run O(E^2) in simulated transfers — 1000-epoch traces were
unreachable.  The :class:`repro.core.stream.StreamingTimeline` keeps the
event-engine state (NIC clear floors + frontier finish times) alive across
``append_epoch`` calls and simulates only the new epoch's transfers, which
the bandwidth-admission theorem makes *byte-identical* to the full
re-simulation (``tests/test_streaming.py`` pins this exactly).

Gates:

* **identity** — an abort-curve-testbed prefix run twice, once with
  ``stream_mode="incremental"`` and once with the retained ``"resim"``
  oracle, produces identical digests, per-epoch commit walls and abort
  breakdowns.
* **trajectory** — a 1000-epoch (quick: 300) diurnal replay: TPC-C load
  modulated by a sinusoidal day cycle (:class:`repro.core.workload.
  DiurnalLoad`); the staleness-feedback read-abort rate must *track* the
  cycle — peak-load phases abort more than trough phases — instead of
  saturating, which is what the long horizon exists to show.
* **scaling** — doubling the horizon costs ~2x wall-clock (O(E)), not ~4x
  (the old O(E^2)).  Gate: ``t(2E) <= 2.5 * t(E)`` with real wall time.
* **occ-vectorized** — ``validate_epoch_detailed``'s numpy fast path beats
  the reference loop on a >=100k-txn epoch while returning an identical
  :class:`~repro.core.occ.ValidationResult`.
* **memory** — O(E) *time* is only half the long-horizon story: with
  ``EngineConfig(keep_epochs=False)`` + ``ServeConfig(keep_epochs=False)``
  the epoch-sink pipeline (``repro.core.sinks``) retains no per-epoch
  state beyond the view/retention frontiers, so doubling the horizon must
  leave the tracemalloc peak flat — gate ``peak(2E) <= 1.1 * peak(E)``
  (the trace itself is a fixed one-day cycle, so input memory is constant
  too).
* **equivalence** — the bounded-memory run's online ``RunSummary``,
  state/value digests, ``ServeStats`` totals/latency distribution and
  trailing ``EpochStats`` window are byte-identical to the retained
  ``keep_epochs=True`` run of the same replay.
"""

from __future__ import annotations

import time
import tracemalloc

import numpy as np

from repro.core import DeltaCRDTStore, Update, Version
from repro.core.occ import Txn, validate_epoch_detailed
from repro.core.workload import DiurnalLoad
from repro.serve import ServeConfig

from .bench_abort_curve import PLANNER
from .bench_throughput import _run_tpcc
from .common import check, paper_testbed

# the abort-curve saturation-boundary cadence: slack enough that the view
# lag breathes with the load cycle instead of diverging (at the native
# 10 ms cadence a 1000-epoch feedback run saturates: abort rate > 0.9
# regardless of load phase, which gates nothing)
DIURNAL_EPOCH_MS = 80.0
DIURNAL_PERIOD = 100       # epochs per simulated "day"
DIURNAL_AMPLITUDE = 0.6    # load swings 0.4x..1.6x around the mean


def _diurnal_run(epochs: int, trace, regions):
    diurnal = {}

    def wrap(gen):
        load = DiurnalLoad(gen, period_epochs=DIURNAL_PERIOD,
                           amplitude=DIURNAL_AMPLITUDE)
        diurnal["load"] = load
        return load

    t0 = time.perf_counter()
    rs, _ = _run_tpcc("TPCC-A", True, trace, regions, epochs=epochs,
                      streaming=True, staleness_feedback=True,
                      epoch_ms=DIURNAL_EPOCH_MS, planner=PLANNER,
                      modeled_cpu=True, load=wrap)
    wall = time.perf_counter() - t0
    return rs, diurnal["load"], wall


def _bounded_run(epochs: int, trace, regions, *, keep_epochs: bool,
                 traced: bool = False):
    """One diurnal feedback + serving replay through the epoch-sink
    pipeline.  ``keep_epochs=False`` is the bounded-memory configuration
    (trailing stats window, online summaries, evicting timeline/serve
    sinks); ``keep_epochs=True`` the retained reference.  With ``traced``
    the tracemalloc peak over the run is returned (bytes, else 0)."""
    serve = ServeConfig(clients_per_node=1_000_000.0, max_staleness_ms=200.0,
                        cache_keys=100, keep_epochs=keep_epochs)

    def wrap(gen):
        return DiurnalLoad(gen, period_epochs=DIURNAL_PERIOD,
                           amplitude=DIURNAL_AMPLITUDE)

    if traced:
        tracemalloc.start()
    try:
        rs, _ = _run_tpcc("TPCC-A", True, trace, regions, epochs=epochs,
                          streaming=True, staleness_feedback=True,
                          epoch_ms=DIURNAL_EPOCH_MS, planner=PLANNER,
                          modeled_cpu=True, serve=serve,
                          keep_epochs=keep_epochs, load=wrap)
        peak = tracemalloc.get_traced_memory()[1] if traced else 0
    finally:
        if traced:
            tracemalloc.stop()
    return rs, peak


def run(quick: bool = True) -> dict:
    horizon = 300 if quick else 1000
    base, regions, trace = paper_testbed(horizon)

    # --- identity: incremental timeline vs the O(E^2) resim oracle -------
    pre = 12
    kw = dict(epochs=pre, streaming=True, staleness_feedback=True,
              epoch_ms=10.0, planner=PLANNER, modeled_cpu=True,
              verify_schedules=True)
    inc, _ = _run_tpcc("TPCC-A", True, trace, regions,
                       stream_mode="incremental", **kw)
    ref, _ = _run_tpcc("TPCC-A", True, trace, regions,
                       stream_mode="resim", **kw)
    same_epochs = all(
        # exact float equality is the point: the incremental timeline is
        # byte-identical to the oracle, not merely close
        (a.stream_commit_ms == b.stream_commit_ms  # lint: allow[float-time-eq]
         and a.wall_ms == b.wall_ms  # lint: allow[float-time-eq]
         and a.read_aborts == b.read_aborts
         and a.ww_aborts == b.ww_aborts
         and a.view_lag_mean == b.view_lag_mean
         and a.view_lag_max == b.view_lag_max)
        for a, b in zip(inc.epochs, ref.epochs)
    )
    identity_ok = (inc.state_digest == ref.state_digest
                   and inc.value_digest == ref.value_digest
                   and same_epochs)

    # --- trajectory + scaling: the diurnal replay itself is the 2E leg ---
    half_rs, _, t_half = _diurnal_run(horizon // 2, trace, regions)
    rs, load, t_full = _diurnal_run(horizon, trace, regions)

    lf = np.array([load.load_factor(e.epoch) for e in rs.epochs])
    rates = np.array([e.read_aborts / e.n_txns if e.n_txns else 0.0
                      for e in rs.epochs])
    # skip the first day: the pipeline warms up from empty NICs
    settled = np.arange(len(rs.epochs)) >= DIURNAL_PERIOD
    peak = float(rates[settled & (lf > 1.1)].mean())
    trough = float(rates[settled & (lf < 0.9)].mean())
    ratio = t_full / t_half

    # --- memory + equivalence: bounded epoch-sink pipeline ---------------
    # a fixed one-day trace cycled by EpochLatencyCycle keeps input memory
    # constant across horizons, so the tracemalloc peak isolates run-state
    # retention: with keep_epochs=False it must stay flat when the horizon
    # doubles
    mem_trace = trace[:DIURNAL_PERIOD]
    mem_half, peak_half = _bounded_run(horizon // 2, mem_trace, regions,
                                       keep_epochs=False, traced=True)
    mem_full, peak_full = _bounded_run(horizon, mem_trace, regions,
                                       keep_epochs=False, traced=True)
    mem_ratio = peak_full / peak_half
    ref_rs, _ = _bounded_run(horizon // 2, mem_trace, regions,
                             keep_epochs=True)
    serve_eq = (
        mem_half.serve.summary() == ref_rs.serve.summary()
        and mem_half.serve.totals == ref_rs.serve.totals
        and np.array_equal(mem_half.serve.latency_values_ms,
                           ref_rs.serve.latency_values_ms)
        and np.array_equal(mem_half.serve.latency_weights,
                           ref_rs.serve.latency_weights)
    )
    window_eq = (len(mem_half.epochs) < len(ref_rs.epochs)
                 and mem_half.epochs == ref_rs.epochs[-len(mem_half.epochs):])
    equivalence_ok = (
        mem_half.summary == ref_rs.summary
        and mem_half.state_digest == ref_rs.state_digest
        and mem_half.value_digest == ref_rs.value_digest
        and serve_eq and window_eq
    )

    # --- occ-vectorized: >=100k-txn epoch, identical result, faster ------
    # mostly-fresh reads (the common regime: only ~5% of reads versioned
    # stale), 3 reads + 2 contended writes per transaction
    rng = np.random.default_rng(7)
    n_txns, n_keys = 100_000, 5_000
    snap = DeltaCRDTStore()
    sv = {}
    for j in range(n_keys):
        v = Version(1, int(rng.integers(40)), int(rng.integers(5)))
        snap.apply(Update(f"k{j}", b"s", v))
        sv[f"k{j}"] = v
    key_draw = rng.integers(n_keys, size=(n_txns, 5))
    stale_txn = rng.random(n_txns) < 0.05
    txns = [
        Txn(txn_id=i, node=int(i % 5), epoch=2, seq=i // 5,
            read_set=tuple(
                (f"k{k}", Version.ZERO if (stale_txn[i] and j == 0)
                 else sv[f"k{k}"])
                for j, k in enumerate(key_draw[i, :3])
            ),
            write_set=tuple((f"k{k}", b"w") for k in key_draw[i, 3:]))
        for i in range(n_txns)
    ]
    t0 = time.perf_counter()
    res_py = validate_epoch_detailed(txns, snap, mode="python")
    t_py = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_np = validate_epoch_detailed(txns, snap, mode="numpy")
    t_np = time.perf_counter() - t0
    speedup = t_py / t_np

    checks = [
        check(identity_ok,
              "identity: incremental timeline == resim oracle on the "
              "abort-curve prefix (digests + per-epoch commits/aborts/lag)",
              f"{pre} epochs at 10 ms cadence, schedules verified"),
        check(peak > trough,
              "trajectory: read-abort rate tracks the diurnal load cycle "
              "(peak phases abort more than trough phases)",
              f"peak {peak:.3f} vs trough {trough:.3f} over "
              f"{horizon} epochs"),
        check(rates[settled].mean() < 0.8,
              "trajectory: the long horizon breathes instead of saturating",
              f"settled mean read-abort rate {rates[settled].mean():.3f}"),
        check(ratio <= 2.5,
              "scaling: doubling the horizon costs ~2x wall (O(E)), "
              "not ~4x (the old O(E^2) re-simulation)",
              f"{horizon // 2}ep {t_half:.1f}s -> {horizon}ep {t_full:.1f}s "
              f"({ratio:.2f}x)"),
        check(mem_ratio <= 1.1,
              "memory: keep_epochs=False holds the tracemalloc peak flat "
              "when the horizon doubles (frontier-bounded retention)",
              f"{horizon // 2}ep {peak_half / 1e6:.1f}MB -> {horizon}ep "
              f"{peak_full / 1e6:.1f}MB ({mem_ratio:.3f}x)"),
        check(equivalence_ok,
              "equivalence: bounded run's online summary, digests, serve "
              "totals/latency distribution and trailing epoch window are "
              "byte-identical to the retained run",
              f"{horizon // 2} epochs, window {len(mem_half.epochs)}"),
        check(res_py == res_np,
              "occ-vectorized: numpy fast path returns an identical "
              "ValidationResult at 100k txns",
              f"{len(res_py.committed)} committed, "
              f"{len(res_py.aborted)} aborted"),
        check(speedup > 1.1,
              "occ-vectorized: measured speedup over the reference loop",
              f"python {t_py:.2f}s vs numpy {t_np:.2f}s ({speedup:.2f}x)"),
    ]
    return {
        "figure": "long-horizon",
        "identity": {"epochs": pre, "ok": identity_ok},
        "diurnal": {
            "horizon": horizon, "epoch_ms": DIURNAL_EPOCH_MS,
            "period_epochs": DIURNAL_PERIOD, "amplitude": DIURNAL_AMPLITUDE,
            "read_abort_peak": peak, "read_abort_trough": trough,
            "read_abort_mean": float(rates[settled].mean()),
            "view_lag_max": max(e.view_lag_max for e in rs.epochs),
            "committed": rs.committed, "total_txns": rs.total_txns,
        },
        "scaling": {"epochs": [horizon // 2, horizon],
                    "wall_s": [round(t_half, 2), round(t_full, 2)],
                    "ratio": round(ratio, 3)},
        "memory": {"epochs": [horizon // 2, horizon],
                   "peak_mb": [round(peak_half / 1e6, 2),
                               round(peak_full / 1e6, 2)],
                   "ratio": round(mem_ratio, 3)},
        "equivalence": {"epochs": horizon // 2,
                        "window": len(mem_half.epochs),
                        "ok": equivalence_ok},
        "occ": {"n_txns": n_txns, "n_keys": n_keys,
                "python_s": round(t_py, 3), "numpy_s": round(t_np, 3),
                "speedup": round(speedup, 2)},
        "checks": checks,
    }


if __name__ == "__main__":
    run(quick=False)
