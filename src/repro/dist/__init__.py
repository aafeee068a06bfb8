"""``repro.dist`` — the device plane of the two-plane synchronization API.

The WAN-simulation plane (``repro.core``) models write-set synchronization
between geo-distributed database replicas; this package is its JAX device
analogue: the ``pod`` mesh axis is the WAN boundary, gradients are the write
sets, and the same strategy names (``flat`` / ``hier`` / ``geococo``)
resolve through the shared registry in ``repro.core.strategies``.

Modules:

* :mod:`~repro.dist.collectives` — ``SyncConfig`` + pod-boundary collectives
* :mod:`~repro.dist.context`     — distribution context for model layers
* :mod:`~repro.dist.sharding`    — per-strategy parameter partitioning
"""

from .collectives import (
    DeviceSyncStrategy,
    SyncConfig,
    chunked_topk_exchange,
    estimate_sync_bytes,
    relay_psum,
    sync_gradients,
)
from .context import DistContext, current, distribution
from .sharding import param_shardings, param_specs

__all__ = [
    "DeviceSyncStrategy",
    "SyncConfig",
    "chunked_topk_exchange",
    "estimate_sync_bytes",
    "relay_psum",
    "sync_gradients",
    "DistContext",
    "current",
    "distribution",
    "param_shardings",
    "param_specs",
]
