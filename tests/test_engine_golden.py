"""Golden values for every GeoCluster engine mode on one seeded workload.

One small run per mode (barrier, event, and the two stream timings with and
without the staleness feedback loop) on a lossy, bandwidth-constrained WAN
with modeled filter CPU, so every number below is deterministic.  Each case
pins the state and value digests and a hash of the per-epoch ``EpochStats``
and the ``ServeStats``.  The values are a recorded reference: any change
to one of them is a change in what that engine computes.
"""

import dataclasses
import hashlib
import numbers

import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    GeoCluster,
    GeoClusterSpec,
    YCSBConfig,
    YCSBGenerator,
    geo_clustered_matrix,
    jitter_trace,
)
from repro.serve import ServeConfig

N_NODES = 5
N_EPOCHS = 10
EPOCH_MS = 40.0
WAN_MBPS = 60.0

# mode -> EngineConfig overrides
MODES = {
    "barrier": dict(barrier=True),
    "event": dict(),
    "incremental": dict(streaming=True),
    "resim": dict(streaming=True, stream_mode="resim"),
    "incremental-serve": dict(streaming=True, serve=True),
    "resim-serve": dict(streaming=True, stream_mode="resim", serve=True),
    "incremental-feedback-serve": dict(streaming=True, staleness_feedback=True,
                                       serve=True),
    "resim-feedback-serve": dict(streaming=True, stream_mode="resim",
                                 staleness_feedback=True, serve=True),
}

# mode -> (state digest, value digest, epoch-stats hash, serve-stats hash)
GOLDEN = {
    'barrier': ('f71b7ea81e94fc73b8887f9868ec42f6e3730c6c0d3b2ba867f87605233b1710',
        'dea625950a4d8e8a5ea9fe33af7dc46f16df17125aae56fe85864d76fb95fe78',
        '6e39412b0b639dbc', 'dc937b59892604f5'),
    'event': ('f71b7ea81e94fc73b8887f9868ec42f6e3730c6c0d3b2ba867f87605233b1710',
        'dea625950a4d8e8a5ea9fe33af7dc46f16df17125aae56fe85864d76fb95fe78',
        'f94c38cb00679743', 'dc937b59892604f5'),
    'incremental': ('f71b7ea81e94fc73b8887f9868ec42f6e3730c6c0d3b2ba867f87605233b1710',
        'dea625950a4d8e8a5ea9fe33af7dc46f16df17125aae56fe85864d76fb95fe78',
        '052f876229306c3d', 'dc937b59892604f5'),
    'resim': ('f71b7ea81e94fc73b8887f9868ec42f6e3730c6c0d3b2ba867f87605233b1710',
        'dea625950a4d8e8a5ea9fe33af7dc46f16df17125aae56fe85864d76fb95fe78',
        '052f876229306c3d', 'dc937b59892604f5'),
    'incremental-serve': ('f71b7ea81e94fc73b8887f9868ec42f6e3730c6c0d3b2ba867f87605233b1710',
        'dea625950a4d8e8a5ea9fe33af7dc46f16df17125aae56fe85864d76fb95fe78',
        '052f876229306c3d', '2b6bf0a4d088dd7a'),
    'resim-serve': ('f71b7ea81e94fc73b8887f9868ec42f6e3730c6c0d3b2ba867f87605233b1710',
        'dea625950a4d8e8a5ea9fe33af7dc46f16df17125aae56fe85864d76fb95fe78',
        '052f876229306c3d', '2b6bf0a4d088dd7a'),
    'incremental-feedback-serve': ('611414d5d881d30100276447f9c660d76f57cf80f07622c6e9a38aff6657f832',
        '54ca3f37cc8fef52e105b220ea311ba1d9f013953b51e2bfff7ad4bf8b057b2c',
        '3f01dd42e4b47c4b', '2b6bf0a4d088dd7a'),
    'resim-feedback-serve': ('611414d5d881d30100276447f9c660d76f57cf80f07622c6e9a38aff6657f832',
        '54ca3f37cc8fef52e105b220ea311ba1d9f013953b51e2bfff7ad4bf8b057b2c',
        '3f01dd42e4b47c4b', '2b6bf0a4d088dd7a'),
}


def _canon(v) -> str:
    """An exact, type-stable text form: floats by their hex bits, so a
    numpy scalar and a Python float of the same value agree."""
    if dataclasses.is_dataclass(v):
        return "{" + ",".join(
            f"{f.name}={_canon(getattr(v, f.name))}"
            for f in dataclasses.fields(v)
        ) + "}"
    if isinstance(v, (list, tuple, np.ndarray)):
        return "[" + ",".join(_canon(x) for x in v) + "]"
    if isinstance(v, (bool, np.bool_)):
        return str(bool(v))
    if isinstance(v, numbers.Integral):
        return str(int(v))
    if isinstance(v, numbers.Real):
        return float(v).hex()
    return repr(v)


def _hash(v) -> str:
    return hashlib.sha256(_canon(v).encode()).hexdigest()[:16]


def _run(mode: str):
    kw = dict(MODES[mode])
    if kw.pop("serve", False):
        kw["serve"] = ServeConfig(clients_per_node=50_000.0,
                                  max_staleness_ms=3 * EPOCH_MS,
                                  cache_keys=50)
    lat, regions = geo_clustered_matrix(
        GeoClusterSpec(n_nodes=N_NODES, n_clusters=2), np.random.default_rng(1)
    )
    trace = jitter_trace(lat, N_EPOCHS, np.random.default_rng(2))
    wan = np.asarray(regions)[:, None] != np.asarray(regions)[None, :]
    bwm = np.where(wan, WAN_MBPS, 10_000.0)
    np.fill_diagonal(bwm, np.inf)
    cfg = EngineConfig(n_nodes=N_NODES, epoch_ms=EPOCH_MS, planner="kcenter",
                       modeled_cpu=True, verify_schedules=True, **kw)
    eng = GeoCluster(cfg, bandwidth_mbps=bwm, loss=0.02, wan_mask=wan, seed=7)
    gen = YCSBGenerator(
        YCSBConfig(n_keys=300, theta=0.9, read_ratio=0.3, hot_write_frac=0.3,
                   hot_locality=True),
        N_NODES, seed=3, node_region=regions,
    )
    return eng.run(gen, trace, txns_per_node=10, n_epochs=N_EPOCHS)


@pytest.mark.parametrize("mode", list(MODES))
def test_engine_mode_matches_golden(mode):
    rs = _run(mode)
    assert len(rs.epochs) == N_EPOCHS
    got = (rs.state_digest, rs.value_digest, _hash(rs.epochs),
           _hash(rs.serve))
    assert got == GOLDEN[mode]
