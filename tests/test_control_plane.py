"""repro.control: NetworkView estimation, ControlPlane events, and the
two-plane subscription wiring (WAN engine + device-plane trainer observing
one plane).  The trainer integration uses 8 forced host devices."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest

from repro.control import (
    ControlPlane,
    LinkDegraded,
    LinkRecovered,
    MonitorView,
    PlanChanged,
    RelayOrderChanged,
    TraceView,
    VivaldiView,
    relay_ring_order,
    ring_cost,
)
from repro.core import EngineConfig, GeoCluster, YCSBConfig, YCSBGenerator
from repro.core.latency import aws_latency_matrix, jitter_trace
from repro.core.monitor import PROBE_BYTES, LatencyMonitor, VivaldiSystem
from repro.core.planner import Replanner, kcenter_grouping


# a 4-node "square": perimeter links 10 ms, diagonals 14 ms.  The bottleneck
# relay ring is the perimeter (0,1,2,3).  Spiking the (0,1) and (2,3) edges
# makes (0,2,1,3) the best ring even under TIV relays — a genuine
# order-changing degradation, not just noise.
SQUARE = np.array(
    [
        [0.0, 10.0, 14.0, 10.0],
        [10.0, 0.0, 10.0, 14.0],
        [14.0, 10.0, 0.0, 10.0],
        [10.0, 14.0, 10.0, 0.0],
    ]
)


def _spiked_square() -> np.ndarray:
    spk = SQUARE.copy()
    spk[0, 1] = spk[1, 0] = 100.0
    spk[2, 3] = spk[3, 2] = 100.0
    return spk


# ---------------------------------------------------------------------------
# NetworkView implementations
# ---------------------------------------------------------------------------


def test_trace_view_playback_and_zero_probe_cost():
    frames = [SQUARE, _spiked_square()]
    v = TraceView(frames, loop=False)
    assert v.n == 4 and v.rounds == 2
    np.testing.assert_array_equal(v.sample(), SQUARE)
    np.testing.assert_array_equal(v.sample(), _spiked_square())
    np.testing.assert_array_equal(v.sample(), _spiked_square())  # tail repeats
    assert v.probe_bytes == 0
    looped = TraceView(frames)  # loop=True default
    looped.sample(), looped.sample()
    np.testing.assert_array_equal(looped.sample(), SQUARE)
    # a single static matrix is a 1-frame trace
    assert TraceView(SQUARE).rounds == 1


def test_monitor_view_symmetry_diag_and_probe_accounting():
    base = aws_latency_matrix()
    trace = jitter_trace(base, 12, np.random.default_rng(0))
    v = MonitorView(TraceView(trace), noise=0.2, rng=np.random.default_rng(1))
    n = v.n
    for r in range(1, 9):
        est = v.sample()
        # noisy probes stay symmetric with a zero diagonal
        np.testing.assert_allclose(est, est.T, rtol=1e-12)
        np.testing.assert_array_equal(np.diag(est), np.zeros(n))
        assert (est >= 0).all()
        # probe-byte accounting is exact: full mesh, n*(n-1) probes/round
        assert v.probe_bytes == r * n * (n - 1) * PROBE_BYTES
    # estimate() pays nothing
    before = v.probe_bytes
    v.estimate()
    assert v.probe_bytes == before


def test_latency_monitor_noise_symmetry_direct():
    truth = aws_latency_matrix()
    mon = LatencyMonitor(10, alpha=0.5)
    rng = np.random.default_rng(2)
    for _ in range(5):
        est = mon.probe_all(truth, rng, noise=0.3)
    np.testing.assert_allclose(est, est.T, rtol=1e-12)
    np.testing.assert_array_equal(np.diag(est), np.zeros(10))
    assert mon.probe_bytes == 5 * 10 * 9 * PROBE_BYTES
    # EWMA converges near truth despite noise
    off = ~np.eye(10, dtype=bool)
    rel = np.abs(est[off] - truth[off]) / truth[off]
    assert np.median(rel) < 0.3


def test_vivaldi_drift_correction():
    """Verification sampling (Sec 5) pins drifting entries: after the truth
    shifts, verify_and_correct beats the raw coordinate estimate."""
    truth = aws_latency_matrix()
    sys = VivaldiSystem(10, seed=0)
    sys.fit(truth, rounds=120, samples_per_node=8, rng=np.random.default_rng(0))
    assert sys.median_rel_error(truth) < 0.25
    # sustained drift: a congestion episode inflates one region's links 3x
    drifted = truth.copy()
    drifted[7, :] *= 3.0
    drifted[:, 7] *= 3.0
    np.fill_diagonal(drifted, 0.0)
    raw = sys.estimate()
    corrected = sys.verify_and_correct(
        drifted, sample_frac=0.5, rng=np.random.default_rng(1), tol=0.25
    )
    iu = np.triu_indices(10, k=1)
    err_raw = np.abs(raw[iu] - drifted[iu]) / drifted[iu]
    err_cor = np.abs(corrected[iu] - drifted[iu]) / drifted[iu]
    assert np.median(err_cor) < np.median(err_raw)
    # corrected entries are pinned to the measurement exactly
    assert (np.abs(corrected[iu] - drifted[iu]) < 1e-9).sum() > 0


def test_vivaldi_view_contract_and_probe_accounting():
    base = aws_latency_matrix()
    v = VivaldiView(TraceView(base), samples_per_node=4, verify_every=3, seed=0)
    n = v.n
    probes = 0
    for r in range(1, 7):
        est = v.sample()
        np.testing.assert_allclose(est, est.T, rtol=1e-12)
        np.testing.assert_array_equal(np.diag(est), np.zeros(n))
        assert (est >= 0).all()
        probes += n * 4  # one sparse round
        if r % 3 == 0:  # plus the verification sample
            n_pairs = n * (n - 1) // 2
            probes += max(1, int(0.05 * n_pairs))
        assert v.probe_bytes == probes * PROBE_BYTES
    # the large-scale regime probes far less than the full mesh
    full = 6 * n * (n - 1) * PROBE_BYTES
    assert v.probe_bytes < full / 2


# ---------------------------------------------------------------------------
# relay-order search
# ---------------------------------------------------------------------------


def test_relay_ring_order_is_canonical_permutation():
    rng = np.random.default_rng(3)
    for n in (2, 3, 5, 8):
        lat = rng.uniform(5.0, 50.0, size=(n, n))
        lat = (lat + lat.T) / 2.0
        np.fill_diagonal(lat, 0.0)
        order = relay_ring_order(lat)
        assert sorted(order) == list(range(n))
        assert order[0] == 0  # canonical start
        if n > 2:
            assert order[1] < order[-1]  # canonical direction


def test_relay_ring_order_bottleneck_objective():
    # line topology 0-1-2-3: any ring must close the long 0..3 loop, but the
    # bottleneck-optimal ring avoids pairing the two far endpoints adjacently
    pos = np.array([0.0, 10.0, 20.0, 30.0])
    lat = np.abs(pos[:, None] - pos[None, :])
    order = relay_ring_order(lat, tiv=False)
    best = min(
        ((0, 1, 2, 3), (0, 1, 3, 2), (0, 2, 1, 3)),
        key=lambda o: ring_cost(lat, o),
    )
    assert order == best
    assert ring_cost(lat, order) <= ring_cost(lat, (0, 1, 2, 3))


def test_relay_ring_order_changes_under_degradation():
    assert relay_ring_order(SQUARE) == (0, 1, 2, 3)
    assert relay_ring_order(_spiked_square()) == (0, 2, 1, 3)


def test_relay_ring_order_scores_direct_hops_by_default():
    """relay_psum executes direct ppermute hops, so the default search must
    score direct latencies: a relay-only-cheap pair (200 ms direct, 2+2 ms
    via a relay) is not a cheap ring hop and must not be ring-adjacent."""
    import itertools

    lat = np.array(
        [
            [0.0, 200.0, 2.0, 8.0],
            [200.0, 0.0, 2.0, 8.0],
            [2.0, 2.0, 0.0, 8.0],
            [8.0, 8.0, 8.0, 0.0],
        ]
    )
    order = relay_ring_order(lat)  # default: direct scoring
    n = len(order)
    edges = {frozenset((order[i], order[(i + 1) % n])) for i in range(n)}
    assert frozenset((0, 1)) not in edges
    # the executed (direct) bottleneck is the optimum over all 4-node rings
    best = min(
        ring_cost(lat, (0,) + p) for p in itertools.permutations((1, 2, 3))
    )
    assert ring_cost(lat, order) == best
    # and the ControlPlane's ring search defaults to direct scoring too
    assert ControlPlane().ring_tiv is False


def test_vivaldi_warmup_seeds_from_direct_rtts():
    """Monitor-seeded warmup: the first K rounds pay the full mesh, return
    the direct measurement, and seed the coordinates — after warmup the
    sparse rounds start near-correct instead of untangling random points."""
    truth = aws_latency_matrix()
    warm = VivaldiView(TraceView(truth), samples_per_node=4, verify_every=100,
                       warmup_rounds=2, seed=0)
    n = warm.n
    est = warm.sample()
    np.testing.assert_allclose(est, truth)          # warmup = direct RTTs
    assert warm.probe_bytes == n * (n - 1) * PROBE_BYTES
    warm.sample()
    assert warm.probe_bytes == 2 * n * (n - 1) * PROBE_BYTES
    # post-warmup: sparse probing only, and the seeded coordinates are
    # already accurate (no 100-round fit needed)
    warm.sample()
    assert warm.probe_bytes == 2 * n * (n - 1) * PROBE_BYTES \
        + n * 4 * PROBE_BYTES
    assert warm.system.median_rel_error(truth) < 0.25
    # a cold view with the same budget of sparse rounds is strictly worse
    cold = VivaldiView(TraceView(truth), samples_per_node=4, verify_every=100,
                       seed=0)
    for _ in range(3):
        cold.sample()
    assert warm.system.median_rel_error(truth) < \
        cold.system.median_rel_error(truth)


# ---------------------------------------------------------------------------
# ControlPlane: damping, events, force contract
# ---------------------------------------------------------------------------


def _square_plane(frames, **kw):
    kw.setdefault("replan_sustain", 2)
    kw.setdefault("degrade_sustain", 2)
    cp = ControlPlane(TraceView(frames, loop=False), **kw)
    events = []
    cp.subscribe(events.append)
    return cp, events


def test_control_plane_damps_transient_spikes():
    spk = _spiked_square()
    # one-round spike between healthy rounds: no replan, no link events
    frames = [SQUARE, SQUARE, spk, SQUARE, SQUARE, SQUARE]
    cp, events = _square_plane(frames, replan_sustain=2, degrade_sustain=2)
    for _ in range(len(frames)):
        cp.step()
    assert cp.replan_count == 1  # only the initial plan
    assert not [e for e in events if isinstance(e, (LinkDegraded, LinkRecovered))]
    assert len([e for e in events if isinstance(e, PlanChanged)]) == 1


def test_control_plane_emits_typed_events_on_sustained_degradation():
    spk = _spiked_square()
    frames = [SQUARE] * 3 + [spk] * 4 + [SQUARE] * 4
    cp, events = _square_plane(frames)
    for _ in range(len(frames)):
        cp.step()
    deg = [e for e in events if isinstance(e, LinkDegraded)]
    rec = [e for e in events if isinstance(e, LinkRecovered)]
    plans = [e for e in events if isinstance(e, PlanChanged)]
    orders = [e for e in events if isinstance(e, RelayOrderChanged)]
    assert {(e.i, e.j) for e in deg} == {(0, 1), (2, 3)}
    assert {(e.i, e.j) for e in rec} == {(0, 1), (2, 3)}
    assert all(e.observed_ms > e.baseline_ms for e in deg)
    assert len(plans) >= 2  # initial + sustained-deviation replan
    assert orders[0].order == (0, 1, 2, 3)
    assert (0, 2, 1, 3) in [e.order for e in orders]
    # event history and counters agree
    assert cp.event_counts()["LinkDegraded"] == 2
    assert cp.events == events


def test_control_plane_subscription_filters_and_unsubscribe():
    frames = [SQUARE] * 3 + [_spiked_square()] * 4
    cp = ControlPlane(TraceView(frames, loop=False), replan_sustain=2,
                      degrade_sustain=2)
    only_plans, everything = [], []
    cp.subscribe(only_plans.append, events=(PlanChanged,))
    fn = cp.subscribe(everything.append)
    for _ in range(4):
        cp.step()
    cp.unsubscribe(fn)
    for _ in range(3):
        cp.step()
    assert all(isinstance(e, PlanChanged) for e in only_plans)
    assert len(only_plans) >= 2
    # the unsubscribed listener missed the tail
    assert len(everything) < len(cp.events)


def test_force_replan_fires_immediately_regression():
    """Regression for the Replanner.force() contract: an event-driven replan
    (straggler signal, operator action) must not wait for the next
    observation."""
    cp, events = _square_plane([SQUARE] * 4)
    cp.step()
    n_before = cp.replan_count
    plan = cp.force_replan(reason="straggler@step7")
    assert plan is not None
    assert cp.replan_count == n_before + 1  # replanned NOW, no observe needed
    forced = [e for e in events if isinstance(e, PlanChanged)
              and e.reason == "straggler@step7"]
    assert len(forced) == 1 and forced[0].plan is plan


def test_bare_replanner_force_without_matrix_waits_for_observe():
    """The documented no-matrix arm: force() alone only flags; the replan
    happens at the next observe()."""
    rp = Replanner(lambda l: kcenter_grouping(l, 2), sustain=2)
    rp.observe(SQUARE)
    assert rp.replan_count == 1
    assert rp.force() is None
    assert rp.replan_count == 1          # nothing happened yet
    rp.observe(SQUARE)                   # matrix unchanged, but force pending
    assert rp.replan_count == 2
    # with a matrix, force is immediate
    assert rp.force(SQUARE) is not None
    assert rp.replan_count == 3


def test_force_replan_with_no_observation_is_noop_without_view():
    cp = ControlPlane(plan_fn=lambda lat: kcenter_grouping(lat, 2))
    assert cp.force_replan() is None
    assert cp.events == []


def _mild_square() -> np.ndarray:
    """(0,1) inflated to 18 ms: trips the per-link detector (>1.5x the 10 ms
    baseline) but stays under the 20% mean-deviation replan threshold —
    a link-only signal, no plan change."""
    mild = SQUARE.copy()
    mild[0, 1] = mild[1, 0] = 18.0
    return mild


def test_link_only_signal_takes_incremental_2opt_path():
    mild = _mild_square()
    frames = [SQUARE] * 2 + [mild] * 3 + [SQUARE] * 3
    cp, events = _square_plane(frames, replan_sustain=3)
    for _ in range(len(frames)):
        cp.step()
    # the mild spike never replanned (damping contract intact)...
    assert cp.replan_count == 1
    assert cp.relay_full_searches == 1        # only the initial global search
    # ...but the sustained link signal repaired the ring incrementally:
    # degraded (0,1) pushed it off the perimeter, recovery restored it
    assert cp.relay_incremental_searches == 2
    assert cp.relay_incremental_evals > 0
    orders = [e.order for e in events if isinstance(e, RelayOrderChanged)]
    assert orders == [(0, 1, 2, 3), (0, 2, 1, 3), (0, 1, 2, 3)]
    assert all(e.reason == "link-event" for e in events
               if isinstance(e, RelayOrderChanged) and e.previous is not None)


def test_incremental_2opt_skips_moves_off_the_signalled_edge():
    """The per-edge contract: only moves touching the degraded edge are
    evaluated.  On an 8-node ring with one off-ring edge degraded, the
    incremental pass evaluates a strict subset of the full 2-opt
    neighborhood and leaves the ring unchanged."""
    rng = np.random.default_rng(5)
    pos = np.arange(8) * 10.0
    lat = np.abs(pos[:, None] - pos[None, :])  # line: ring is 0..7
    lat = lat + rng.uniform(0.0, 1.0, size=lat.shape)
    lat = (lat + lat.T) / 2.0
    np.fill_diagonal(lat, 0.0)
    spiked = lat.copy()
    spiked[0, 7] = spiked[7, 0] = lat[0, 7] * 1.8   # already the worst hop's
    frames = [lat] * 2 + [spiked] * 3               # antipodal chord
    cp, events = _square_plane(frames, replan_sustain=10)
    for _ in range(len(frames)):
        cp.step()
    n = 8
    full_neighborhood = n * (n - 3) // 2  # all 2-opt moves on an 8-ring
    assert cp.relay_incremental_searches >= 1
    per_sweep = cp.relay_incremental_evals / cp.relay_incremental_searches
    assert per_sweep < full_neighborhood
    assert cp.relay_full_searches == 1


def test_node_failure_flows_through_the_plane():
    cp, events = _square_plane([SQUARE] * 4)
    cp.step()
    victim = cp.plan.aggregators[0]
    plan = cp.on_node_failure(victim)
    assert victim not in [a for g in plan.groups for a in g]
    fails = [e for e in events if isinstance(e, PlanChanged)
             and e.reason.startswith("node-failure")]
    assert len(fails) == 1
    # full regroup at the next observation (the no-matrix force arm)
    n = cp.replan_count
    cp.step()
    assert cp.replan_count == n + 1


# ---------------------------------------------------------------------------
# two-plane wiring
# ---------------------------------------------------------------------------


def _tiny_cluster(control=None, n=4, seed=0):
    eng = GeoCluster(
        EngineConfig(n_nodes=n, sync_strategy="geococo", planner="kcenter"),
        control=control, bandwidth_mbps=200.0, seed=seed,
    )
    gen = YCSBGenerator(YCSBConfig(n_keys=300, theta=0.8), n, seed=seed)
    return eng, gen


def test_engine_owns_no_private_replanner():
    eng, gen = _tiny_cluster()
    frames = np.stack([SQUARE] * 2 + [_spiked_square()] * 4)
    rs = eng.run(gen, frames, txns_per_node=4)
    # the plan came from the control plane, not a private replanner
    assert eng.control.replan_count >= 1
    assert eng.control.plan is not None
    assert rs.committed > 0


def test_engine_binds_payload_planner_only_on_default_plane():
    cp = ControlPlane(plan_fn=lambda lat: kcenter_grouping(lat, 2))
    eng, _ = _tiny_cluster(control=cp)
    # an explicit planner on a shared plane is kept
    assert cp.replanner.plan_fn != eng._plan_fn
    cp2 = ControlPlane()
    eng2, _ = _tiny_cluster(control=cp2)
    assert cp2.replanner.plan_fn == eng2._plan_fn


def test_both_planes_observe_the_same_event_instances():
    """Acceptance: one ControlPlane; the WAN engine drives observations and
    a device-plane-style subscriber receives the *same* PlanChanged events."""
    cp = ControlPlane(replan_sustain=2, degrade_sustain=2)
    device_side = []
    cp.subscribe(device_side.append, events=(PlanChanged, RelayOrderChanged))
    eng, gen = _tiny_cluster(control=cp)
    assert eng.control is cp
    frames = np.stack([SQUARE] * 3 + [_spiked_square()] * 4)
    eng.run(gen, frames, txns_per_node=4)
    plans = [e for e in device_side if isinstance(e, PlanChanged)]
    assert len(plans) >= 2  # initial + sustained-deviation
    # identity: the device side holds the exact event objects in history
    for e in plans:
        assert any(e is h for h in cp.events)
    # and the engine's current plan is the last PlanChanged payload
    assert plans[-1].plan is cp.plan


# ---------------------------------------------------------------------------
# trainer integration (device plane) — 8 forced host devices
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pod4_mesh():
    import jax

    from repro.launch.mesh import make_small_mesh

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    return make_small_mesh((4, 2), ("pod", "data"))


def _mk_trainer(mesh, control, steps=8, strategy="geococo"):
    from repro.configs.registry import get_smoke_config
    from repro.data.pipeline import DataConfig
    from repro.dist.collectives import SyncConfig
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_smoke_config("minitron-8b")
    tcfg = TrainConfig(
        sync=SyncConfig(strategy=strategy, density=0.25, chunk=64,
                        min_leaf_size=64),
        optim=AdamWConfig(lr=1e-3, total_steps=steps, warmup_steps=2),
    )
    run_cfg = TrainerConfig(steps=steps, log_every=100)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    return Trainer(cfg, mesh, tcfg, run_cfg, data_cfg, control=control)


def test_trainer_relay_order_follows_control_events(pod4_mesh):
    """Acceptance: a geococo Trainer under an injected latency-spike trace
    changes relay_psum's ring order via a ControlPlane RelayOrderChanged
    event, rebuilds its step, and keeps training."""
    frames = [SQUARE] * 2 + [_spiked_square()] * 8
    cp = ControlPlane(TraceView(frames, loop=False), replan_sustain=2,
                      degrade_sustain=2)
    tr = _mk_trainer(pod4_mesh, cp)
    hist = tr.run()
    orders = [e.order for e in tr.network_events
              if isinstance(e, RelayOrderChanged)]
    assert orders[0] == (0, 1, 2, 3)          # measured pre-spike ring
    assert tr.tcfg.sync.ring_order == relay_ring_order(_spiked_square())
    assert len(set(orders)) >= 2              # the order demonstrably changed
    assert tr.sync_rebuilds >= 2              # each change rebuilt the step
    assert len(hist) == 8
    assert np.isfinite(hist[-1]["loss"]) and hist[-1]["loss"] < hist[0]["loss"]


def test_trainer_straggler_trip_forces_immediate_replan(pod4_mesh):
    frames = [SQUARE] * 12
    cp = ControlPlane(TraceView(frames, loop=False), replan_sustain=3)
    tr = _mk_trainer(pod4_mesh, cp, steps=4)
    tr.monitor.threshold = 0.0  # trip on every observed step
    tr.monitor.sustain = 1
    tr.run()
    forced = [e for e in cp.events if isinstance(e, PlanChanged)
              and e.reason.startswith("straggler@")]
    assert len(forced) >= 1  # the trip replanned without waiting a round


