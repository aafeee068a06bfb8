"""The read serving plane (``repro.serve``).

Covers the three layers separately and wired together:

* ``ServeConfig`` validation and the streaming-only engine gate,
* ``simulate_serving`` on synthetic commit matrices — staleness-bound
  semantics, redirect/reject policies, cache-aside accounting, latency
  percentiles, and the exact monotonicity theorems the benchmark gates on,
* ``GeoCluster`` integration — ``RunStats.serve`` population and the
  digest-neutrality regression (the serving plane reads the measured
  ``node_commit_ms`` matrix post hoc; it must never perturb commits).
"""

import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    GeoCluster,
    GeoClusterSpec,
    TPCCConfig,
    TPCCGenerator,
    geo_clustered_matrix,
    jitter_trace,
)
from repro.core.workload import ZipfianSampler
from repro.serve import (
    ServeConfig,
    simulate_serving,
    view_epochs,
    view_staleness_ms,
    weighted_percentile,
)


# ---------------------------------------------------------------------------
# config / wiring
# ---------------------------------------------------------------------------


def test_serve_requires_streaming():
    """The serving plane reads the stitched simulation's per-node commit
    times; without streaming there is no such measurement."""
    with pytest.raises(ValueError, match="streaming"):
        EngineConfig(n_nodes=4, serve=ServeConfig())
    # streaming=True accepts it
    EngineConfig(n_nodes=4, streaming=True, serve=ServeConfig())


def test_unknown_policy_fails_fast():
    with pytest.raises(KeyError, match="serve_policy"):
        ServeConfig(policy="nope")


@pytest.mark.parametrize("kw", [
    dict(read_ratio=1.5),
    dict(max_staleness_ms=-1.0),
    dict(ops_per_client_s=0.0),
    dict(clients_per_node=-5.0),
    dict(cache_keys=200, n_keys=100),
])
def test_config_validation(kw):
    with pytest.raises(ValueError):
        ServeConfig(**kw)


def test_per_node_client_populations():
    cfg = ServeConfig(clients_per_node=[1e6, 2e6, 0.0], ops_per_client_s=2.0,
                      read_ratio=0.75)
    reads = cfg.reads_per_epoch(3, epoch_ms=10.0)
    # 1e6 clients * 2 ops/s * 10ms = 20_000 ops, 75% reads
    assert np.allclose(reads, [15_000.0, 30_000.0, 0.0])
    assert np.allclose(cfg.writes_per_epoch(3, 10.0), [5_000.0, 10_000.0, 0.0])
    with pytest.raises(ValueError, match="shape"):
        cfg.clients(4)


def test_weighted_percentile():
    v = np.array([1.0, 10.0, 100.0])
    w = np.array([98.0, 1.0, 1.0])
    assert weighted_percentile(v, w, 50.0) == 1.0
    assert weighted_percentile(v, w, 99.0) == pytest.approx(10.0)
    assert weighted_percentile(v, w, 100.0) == 100.0
    assert weighted_percentile(np.array([]), np.array([]), 50.0) == 0.0


# ---------------------------------------------------------------------------
# simulate_serving on synthetic commit matrices
# ---------------------------------------------------------------------------

# 3 nodes, 4 epochs, 10 ms cadence.  Node 0 commits almost immediately,
# node 1 lags ~1 epoch, node 2 lags several epochs — a WAN-backlogged tail.
_COMMIT = np.array([
    [1.0, 12.0, 40.0],
    [11.0, 22.0, 80.0],
    [21.0, 32.0, 120.0],
    [31.0, 42.0, 160.0],
])
_LAT = np.array([
    [0.0, 20.0, 80.0],
    [20.0, 0.0, 60.0],
    [80.0, 60.0, 0.0],
])


def _serve(bound, *, policy="redirect", cache_keys=0, epoch_ms=10.0,
           commit=_COMMIT, clients=1e6):
    cfg = ServeConfig(clients_per_node=clients, max_staleness_ms=bound,
                      policy=policy, cache_keys=cache_keys)
    return simulate_serving(cfg, commit, [_LAT] * commit.shape[0],
                            epoch_ms, wall_ms=commit.max())


def test_view_staleness_from_commit_matrix():
    # at t=30 (epoch 3's arrival): node0 merged epochs {0,1,2} -> fresh,
    # node1 merged {0,1} -> 10 ms behind, node2 merged nothing -> 30 ms
    assert list(view_epochs(_COMMIT, 30.0)) == [3, 2, 0]
    assert np.allclose(view_staleness_ms(_COMMIT, 30.0, 10.0), [0.0, 10.0, 30.0])
    # boundary convention matches advance_views: commit at exactly `now`
    # counts as delivered
    assert list(view_epochs(np.array([[5.0]]), 5.0)) == [1]


def test_redirect_policy_routes_to_freshest_replica():
    s = _serve(5.0)
    # epoch 0: everyone fresh (staleness 0).  Epochs 1-3: node 0 is the only
    # one within the 5 ms bound; nodes 1,2 redirect to it and are served.
    assert s.rejected == 0.0
    assert s.redirected == pytest.approx(3 * 2 * 9500.0)  # 3 epochs, 2 nodes
    assert s.served_reads == s.reads_total
    # redirected reads pay the RTT: the tail is fatter than the local median
    assert s.read_latency_p99_ms > s.read_latency_p50_ms
    assert s.read_latency_p99_ms >= 2 * 60.0  # node2 -> node0 RTT is 160
    assert s.throughput_rps == pytest.approx(s.reads_total / (s.wall_ms / 1e3))


def test_redirect_rejects_when_no_replica_is_fresh_enough():
    # shift every commit late: at each arrival time *no* node has merged the
    # previous epoch, so even the freshest replica violates a 0-bound
    late = _COMMIT + 1000.0
    s = _serve(0.0, commit=late)
    assert s.epochs[0].rejected == 0.0  # epoch 0: empty prefix == fresh
    assert all(e.rejected == e.reads > 0 for e in s.epochs[1:])
    assert s.rejected == s.redirected  # reject set == attempted redirects


def test_reject_policy_never_redirects():
    s = _serve(5.0, policy="reject")
    assert s.redirected == 0.0
    assert s.rejected == pytest.approx(3 * 2 * 9500.0)
    assert s.served_reads == s.reads_total - s.rejected
    # only local latencies in the distribution
    assert s.read_latency_p99_ms == pytest.approx(ServeConfig().local_read_ms)


def test_zero_bound_zero_lag_serves_everything_locally():
    """The satellite-3 unit test: ``max_staleness_ms=0`` with zero view lag
    (every commit lands before the next arrival) serves every read locally —
    no redirects, no rejects, no stale serves."""
    # commit_ms[e, i] < (e+1)*epoch_ms for all nodes -> views always caught up
    commit = np.array([[1.0, 2.0, 3.0], [11.0, 12.0, 13.0], [21.0, 22.0, 23.0]])
    s = _serve(0.0, commit=commit, epoch_ms=10.0)
    assert s.redirected == 0.0
    assert s.rejected == 0.0
    assert s.stale_served == 0.0
    assert s.served_local == s.reads_total == s.served_reads
    assert s.redirect_rate == 0.0 and s.stale_serve_rate == 0.0


def test_cache_hit_rate_matches_zipf_top_mass():
    s = _serve(1e9, cache_keys=100)
    sampler = ZipfianSampler(ServeConfig().n_keys, ServeConfig().zipf_theta,
                             np.random.default_rng(0))
    assert s.cache_hit_rate == pytest.approx(sampler.top_mass(100))
    # hits are strictly cheaper than misses, so the median drops
    assert s.read_latency_p50_ms == ServeConfig().cache_hit_ms
    no_cache = _serve(1e9)
    assert no_cache.cache_hit_rate == 0.0
    assert no_cache.read_latency_p50_ms == ServeConfig().local_read_ms


def test_bound_monotonicity_exact():
    """The benchmark's gates as exact theorems on one commit matrix:
    loosening the staleness bound never decreases served reads or stale
    serves, never increases redirects or rejects."""
    grid = [0.0, 5.0, 10.0, 15.0, 25.0, 40.0, 1e9]
    for policy in ("redirect", "reject"):
        runs = [_serve(b, policy=policy) for b in grid]
        for a, b in zip(runs, runs[1:]):
            assert b.served_reads >= a.served_reads
            assert b.stale_served >= a.stale_served
            assert b.redirected <= a.redirected
            assert b.rejected <= a.rejected
        # conservation: every read is served or rejected
        for r in runs:
            assert r.served_reads + r.rejected == pytest.approx(r.reads_total)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _run_engine(serve=None, *, feedback=False, streaming=True, epoch_ms=2.0):
    lat, regions = geo_clustered_matrix(
        GeoClusterSpec(n_nodes=5, n_clusters=2), np.random.default_rng(1)
    )
    trace = jitter_trace(lat, 8, np.random.default_rng(2))
    wan = np.asarray(regions)[:, None] != np.asarray(regions)[None, :]
    bwm = np.where(wan, 20.0, 10_000.0)
    np.fill_diagonal(bwm, np.inf)
    cfg = EngineConfig(n_nodes=5, streaming=streaming,
                       staleness_feedback=feedback, grouping=True,
                       filtering=True, tiv=True, planner="kcenter",
                       epoch_ms=epoch_ms, serve=serve, modeled_cpu=True)
    eng = GeoCluster(cfg, bandwidth_mbps=bwm, wan_mask=wan, seed=7)
    gen = TPCCGenerator(
        TPCCConfig(n_warehouses=20, mix="TPCC-A", remote_prob=0.25,
                   items_per_warehouse=20),
        5, seed=3,
    )
    return eng.run(gen, trace, txns_per_node=10, n_epochs=8)


def test_engine_populates_serve_stats_and_stays_digest_neutral():
    off = _run_engine()
    on = _run_engine(ServeConfig(clients_per_node=1e6, max_staleness_ms=50.0,
                                 cache_keys=100))
    assert off.serve is None
    assert on.serve is not None
    assert on.serve.reads_total > 0
    assert on.serve.epochs and len(on.serve.epochs) == 8
    # the serving plane is an observer of node_commit_ms: commit content,
    # byte accounting and timing are untouched
    assert on.state_digest == off.state_digest
    assert on.value_digest == off.value_digest
    assert on.committed == off.committed
    assert on.wan_bytes == off.wan_bytes
    assert [e.wall_ms for e in on.epochs] == [e.wall_ms for e in off.epochs]


def test_engine_serve_under_staleness_feedback():
    """Serving composes with the OCC feedback loop: same measured commit
    signal drives both read-abort staleness and serve-plane staleness."""
    rs = _run_engine(ServeConfig(clients_per_node=1e6, max_staleness_ms=50.0),
                     feedback=True)
    assert rs.serve is not None
    # the 2 ms cadence is far below the WAN makespan: views lag, so the
    # plane must observe nonzero staleness somewhere
    assert rs.serve.stale_served + rs.serve.redirected + rs.serve.rejected > 0
    assert max(e.view_staleness_ms_max for e in rs.serve.epochs) > 0


def test_engine_slack_cadence_serves_fresh():
    """At a cadence above the sync makespan every view is caught up by the
    next arrival: the plane serves everything locally and fresh even at a
    zero staleness bound (the engine-level satellite-3 check)."""
    rs = _run_engine(ServeConfig(clients_per_node=1e6, max_staleness_ms=0.0),
                     epoch_ms=2_000.0)
    s = rs.serve
    assert s.redirected == 0.0 and s.rejected == 0.0 and s.stale_served == 0.0
    assert s.served_local == s.reads_total


def test_non_streaming_engines_never_serve():
    rs = _run_engine(None, streaming=False)
    assert rs.serve is None
