"""Device-plane synchronization: GeoCoCo's three levers over the mesh
``pod`` axis (the WAN analogue of the training stack).

This is the device-plane half of the two-plane strategy surface (see
``repro.core.strategies``):

* **grouping / hierarchy** (paper Sec 4.2): ``hier`` syncs FSDP-scattered
  gradient shards instead of full replicas, and :func:`relay_psum` expresses
  the aggregator relay ring (TIV-exploiting overlay paths map to the ring
  ``order``);
* **task-preserving filtering** (Sec 4.3): ``geococo`` runs
  :func:`chunked_topk_exchange` — density-based top-k selection with
  error-feedback residuals, the gradient analogue of white-data removal
  (dropped mass is *carried*, not lost, so the training task is preserved);
* **consistency-guaranteed transmission** (Sec 4.4): every strategy is a
  deterministic collective — all pods hold identical synced gradients after
  the exchange, mirroring the epoch-commit guarantee of the WAN plane.

Strategies register under ``("device_sync", name)`` in the shared registry,
so the WAN plane (``EngineConfig``) and the device plane (``SyncConfig``)
resolve the *same names* — ``flat`` / ``hier`` / ``geococo``.

:func:`estimate_sync_bytes` is the analytic wire model the benchmarks
cross-check against the WAN simulator and against bytes actually moved by
:func:`sync_gradients`.

Deployment note: the train step (``repro.train.train_step``) takes each
pod's loss and gradient from that pod's own rows of the batch, inside a
``shard_map`` manual over ``pod``, so the gradients reaching
``sync_gradients`` differ by pod as they do between regions, and nothing
else crosses the pod axis but the scalar loss mean.  ``geococo``'s
error-feedback residuals therefore differ by pod too.  Each chip
exchanges its own FSDP/TP shard of a leaf wherever the shard is a whole
number of the filter's ``chunk`` rows (:func:`shard_local_specs`): it
filters and all-reduces over ``pod`` just those rows, which are the whole
leaf's rows, so the result is the same to the bit.  Any other leaf (the
unsharded ones, a misaligned one, a shard under ``min_leaf_size``) enters
whole on every chip of the pod.  The exchange still moves dense bytes:
:func:`chunked_topk_exchange` masks the unsent entries to zero and
``pmean``s the whole array, so the sparsification changes the update, and
:func:`estimate_sync_bytes` gives the (value, index) bytes a sparse
transport would move, not what the collective moves today.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import strategies

__all__ = [
    "SyncConfig",
    "DeviceSyncStrategy",
    "sync_gradients",
    "shard_local_specs",
    "exchange_local_share",
    "relay_psum",
    "chunked_topk_exchange",
    "estimate_sync_bytes",
]

_INDEX_BYTES = 4  # chunk-local top-k index cost per transmitted value


# ---------------------------------------------------------------------------
# strategy objects + registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceSyncStrategy:
    """One named gradient-exchange strategy.

    ``wire_values(n, cfg, shard_factor)`` returns ``(dense_values,
    sparse_values)`` — how many dense values and how many (value, index)
    pairs of an ``n``-element leaf cross the pod boundary per all-reduce;
    the split keeps the analytic estimator and the measured nonzero counts
    comparable.  ``shard_factor`` is how many in-pod devices a leaf is
    split across: the filter's ``min_leaf_size`` / chunking decisions
    happen on the shard each device actually holds.

    ``react(cfg, event)`` declares how the strategy responds to a
    ``repro.control`` :class:`~repro.control.events.NetworkEvent`: it
    returns an updated :class:`SyncConfig` (the trainer then rebuilds its
    step) or ``None`` for "no reaction".  ``flat`` ignores the network
    (replicated all-to-all has no ring to re-route); ``hier`` and
    ``geococo`` adopt the control plane's relay ring on
    :class:`~repro.control.events.RelayOrderChanged`.
    """

    name: str
    needs_residuals: bool
    wire_values: Callable[[float, "SyncConfig", float], tuple[float, float]]
    react: Callable[["SyncConfig", Any], "SyncConfig | None"] | None = None


def _dense_wire(n: float, cfg: "SyncConfig", shard_factor: float = 1.0):
    return float(n), 0.0


def _topk_wire(n: float, cfg: "SyncConfig", shard_factor: float = 1.0):
    local_n = n / max(shard_factor, 1.0)
    if local_n < cfg.min_leaf_size:
        return float(n), 0.0  # small (per-shard) leaves are exchanged densely
    n_chunks = math.ceil(local_n / cfg.chunk)
    k = max(1, int(round(cfg.density * cfg.chunk)))
    return 0.0, float(n_chunks * min(k, cfg.chunk) * max(shard_factor, 1.0))


def _react_relay_order(cfg: "SyncConfig", event: Any) -> "SyncConfig | None":
    """Ring-bearing strategies adopt the control plane's new relay order."""
    from ..control.events import RelayOrderChanged

    if isinstance(event, RelayOrderChanged):
        order = tuple(int(i) for i in event.order)
        if order != cfg.ring_order:
            return dataclasses.replace(cfg, ring_order=order)
    return None


strategies.register(
    "device_sync", "flat",
    DeviceSyncStrategy("flat", needs_residuals=False, wire_values=_dense_wire),
)
strategies.register(
    "device_sync", "hier",
    DeviceSyncStrategy("hier", needs_residuals=False, wire_values=_dense_wire,
                       react=_react_relay_order),
)
strategies.register(
    "device_sync", "geococo",
    DeviceSyncStrategy("geococo", needs_residuals=True, wire_values=_topk_wire,
                       react=_react_relay_order),
)


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """Device-plane sync strategy configuration.

    ``strategy`` must name a registered ``device_sync`` strategy.  ``density``
    is the kept fraction per chunk for the filtered exchange; ``chunk`` the
    top-k selection granularity; ``min_leaf_size`` the element count below
    which a leaf skips filtering (norm scales and biases are cheap and
    high-impact — always sent densely, a task-preservation choice).

    ``ring_order`` is the pod relay ring for the exchange — the device-plane
    image of the WAN plane's TIV relay paths, normally fed by
    ``repro.control.ControlPlane`` from *measured* inter-pod latency (a
    :class:`RelayOrderChanged` event through the strategy's ``react``).
    ``None`` keeps the pmean default (ring order left to XLA).
    """

    strategy: str = "hier"
    density: float = 0.10
    chunk: int = 2048
    min_leaf_size: int = 4096
    ring_order: tuple[int, ...] | None = None

    def __post_init__(self):
        known = strategies.names("device_sync")
        if self.strategy not in known:
            raise ValueError(
                f"unknown sync strategy {self.strategy!r}; registered: {known}"
            )
        if not (0.0 < self.density <= 1.0):
            raise ValueError(f"density must be in (0, 1], got {self.density}")
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if self.min_leaf_size < 0:
            raise ValueError(
                f"min_leaf_size must be >= 0, got {self.min_leaf_size}"
            )
        if self.ring_order is not None:
            order = tuple(int(i) for i in self.ring_order)
            if sorted(order) != list(range(len(order))):
                raise ValueError(
                    f"ring_order must be a permutation of 0..n_pods-1, "
                    f"got {self.ring_order}"
                )
            object.__setattr__(self, "ring_order", order)

    @property
    def spec(self) -> DeviceSyncStrategy:
        return strategies.get("device_sync", self.strategy)

    @property
    def needs_residuals(self) -> bool:
        return self.spec.needs_residuals


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------


def relay_psum(x: jnp.ndarray, axis: str = "pod", *, order=None) -> jnp.ndarray:
    """All-reduce over ``axis`` via an explicit relay ring.

    ``order`` is the ring order of pod indices — the device-plane mirror of
    the WAN plane's TIV relay paths (``repro.core.latency.one_relay_effective``):
    a profitable overlay path becomes the ring neighbor ordering, so the
    slowest direct pair never carries traffic.  The result equals
    ``jax.lax.psum`` (up to float reassociation).
    """
    if order is not None:
        n = len(order)
    else:
        n = int(jax.lax.psum(1, axis))
        order = tuple(range(n))
    if n <= 1:
        return x
    perm = [(int(order[i]), int(order[(i + 1) % n])) for i in range(n)]
    acc = x
    msg = x
    for _ in range(n - 1):
        msg = jax.lax.ppermute(msg, axis, perm=perm)
        acc = acc + msg
    return acc


def _pod_mean(x: jnp.ndarray, axis: str, n_pods: int, order) -> jnp.ndarray:
    """Mean over pods — through the explicit relay ring when an order is
    set (measured-latency routing), else the stock ``pmean``."""
    if order is None:
        return jax.lax.pmean(x, axis)
    return relay_psum(x, axis, order=order) / n_pods


def _topk_mask(m: jnp.ndarray, k: int) -> jnp.ndarray:
    """Boolean per-row mask selecting the ``k`` largest-|.| entries of ``m``.

    The entries ``top_k`` picks, built by a compare rather than a scatter of
    its indices: every entry above the row's k-th value, and every entry
    equal to it up to the column of the last index ``top_k`` returned
    (``top_k`` puts the lower index first among equals).  ``|m|`` is
    compared by its int32 bit pattern, a total order on non-negative floats
    that ranks NaN above +inf, as ``top_k`` does.
    """
    chunk = m.shape[1]
    if k >= chunk:
        return jnp.ones(m.shape, bool)
    a = jnp.abs(m)
    vals, idx = jax.lax.top_k(a, k)                            # (rows, k)
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    kth = jax.lax.bitcast_convert_type(vals[:, k - 1:], jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    return (bits > kth) | ((bits == kth) & (col <= idx[:, k - 1:]))


def chunked_topk_exchange(
    grad: jnp.ndarray,
    residual: jnp.ndarray | None,
    *,
    axis: str = "pod",
    density: float = 0.10,
    chunk: int = 2048,
    order: tuple[int, ...] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Density-based top-k gradient exchange with error feedback.

    The device-plane analogue of white-data filtering: per ``chunk``-sized
    block, only the ``density`` fraction of largest-magnitude entries of
    ``grad + residual`` crosses the pod boundary: exactly
    ``k = max(1, round(density * chunk))`` entries a row (the tail row
    padded with zeros), the lower index first among equal magnitudes, as
    ``jax.lax.top_k`` orders them.  The rest stays in the new residual and
    is *carried to the next step* (error feedback), so no task
    signal is dropped — only deferred.  Returns ``(pmean_of_sent,
    new_residual)``.  With ``density=1.0`` this is exactly a ``pmean`` and
    the residual returns to zero.  ``order`` routes the reduction over an
    explicit relay ring (see :func:`relay_psum`); the result is identical
    up to float reassociation.
    """
    dtype = grad.dtype
    acc = grad.astype(jnp.float32)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    shape = acc.shape
    flat = acc.ravel()
    n = flat.size
    pad = (-n) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    m = flat.reshape(-1, chunk)
    k = max(1, int(round(density * chunk)))
    pick = _topk_mask(m, k)
    # ``m * mask`` to the bit: an unsent entry goes out as ``m * 0.0``, a
    # zero of its own sign (NaN for an inf).  XLA rewrites a product with a
    # converted boolean into a select of +0.0, so it is written out here.
    sent = jnp.where(pick, m, m * 0.0)
    new_res = m - sent
    if order is not None:
        out = relay_psum(sent, axis, order=order) / len(order)
    else:
        out = jax.lax.pmean(sent, axis)
    out = out.ravel()[:n].reshape(shape).astype(dtype)
    new_res = new_res.ravel()[:n].reshape(shape)
    return out, new_res


def sync_gradients(
    grads: Any,
    residuals: Any,
    cfg: SyncConfig,
    *,
    axis: str = "pod",
    n_pods: int | None = None,
) -> tuple[Any, Any]:
    """Synchronize a gradient pytree across pods under ``cfg.strategy``.

    Must run where ``axis`` is a bound (manual) mesh axis when
    ``n_pods > 1`` — e.g. inside a ``shard_map`` over the pod axis.
    ``grads`` and ``residuals`` are this pod's own: its gradient from its
    own rows of the batch, and its error-feedback state, which differs by
    pod.  Every pod returns the same synced gradients (the mean over pods
    of what each sent) and its own new residuals.  The collectives move
    dense arrays (unsent entries as zeros).  With a single pod this is the
    identity (the input objects are returned untouched).

    Each leaf is exchanged as the calling region holds it.  The train step
    hands each chip its own shard of the leaves :func:`shard_local_specs`
    marks, which it filters and all-reduces alone, and the whole of every
    other leaf, which each chip of a pod filters alike.

    Returns ``(synced_grads, new_residuals)``.  ``new_residuals`` is ``None``
    whenever ``residuals`` is ``None`` and the strategy carries no state.
    """
    if n_pods is None or n_pods <= 1:
        return grads, residuals
    order = cfg.ring_order
    if order is not None and len(order) != n_pods:
        raise ValueError(
            f"ring_order {order} does not cover the {n_pods}-pod axis"
        )
    spec = cfg.spec
    if not spec.needs_residuals:
        synced = jax.tree.map(
            lambda g: _pod_mean(g, axis, n_pods, order), grads
        )
        return synced, residuals

    res = residuals
    if res is None:
        res = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, r):
        if g.size < cfg.min_leaf_size:
            return _pod_mean(g, axis, n_pods, order), r
        return chunked_topk_exchange(
            g, r, axis=axis, density=cfg.density, chunk=cfg.chunk, order=order
        )

    flat_g, td = jax.tree.flatten(grads)
    flat_r = td.flatten_up_to(res)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    synced = td.unflatten([o[0] for o in out])
    new_res = td.unflatten([o[1] for o in out])
    return synced, new_res


def _ways(part, mesh_shape) -> int:
    """How many shards one entry of a ``PartitionSpec`` cuts its dim into."""
    names = () if part is None else part if isinstance(part, tuple) else (part,)
    return math.prod(mesh_shape[a] for a in names)


def shard_local_specs(leaves: Any, specs: Any, mesh_shape, cfg: SyncConfig) -> Any:
    """The in-pod ``PartitionSpec`` with which each leaf of a gradient
    enters the exchange: its own spec where each chip may filter its shard
    alone, ``P()`` (the whole leaf on every chip) elsewhere.

    The filter cuts a leaf's row-major flatten into rows of ``cfg.chunk``.
    In that flatten a shard is a series of contiguous runs of
    ``shape[d] // s * prod(shape[d+1:])`` elements, ``d`` the innermost dim
    the spec splits ``s`` ways, each run starting at a multiple of its
    length.  Where the run is a multiple of ``chunk``, the shard's own
    flatten cut into rows is a subset of the whole leaf's rows, in order;
    top-k is per row, so what is sent, the pod mean and the residual are the
    same to the bit.  The shard must also hold ``cfg.min_leaf_size``
    elements, so that ``sync_gradients``, which chooses between the dense
    mean and the filter on the size it sees, chooses as for the whole leaf.
    """

    def one(leaf, spec):
        ways = [_ways(part, mesh_shape) for part in spec]
        shards = math.prod(ways)
        if shards == 1:
            return P()
        d = max(i for i, w in enumerate(ways) if w > 1)
        run = leaf.shape[d] // ways[d] * math.prod(leaf.shape[d + 1:])
        if run % cfg.chunk or leaf.size // shards < cfg.min_leaf_size:
            return P()
        return spec

    return jax.tree.map(one, leaves, specs)


def exchange_local_share(leaves: Any, plan: Any) -> float:
    """The share of the elements of ``leaves`` that :func:`shard_local_specs`'s
    ``plan`` exchanges on their shard (0 for no plan)."""
    if plan is None:
        return 0.0
    pairs = list(zip(jax.tree.leaves(leaves), jax.tree.leaves(plan)))
    local = sum(leaf.size for leaf, spec in pairs if spec != P())
    return local / sum(leaf.size for leaf, _ in pairs)


# ---------------------------------------------------------------------------
# analytic wire model
# ---------------------------------------------------------------------------


def estimate_sync_bytes(
    n_params: float | Any,
    cfg: SyncConfig,
    n_pods: int,
    *,
    bytes_per_value: int = 4,
    shard_factor: float = 1.0,
) -> float:
    """Analytic inter-pod bytes per device per step.

    ``n_params`` is either an element count (the per-device shard size the
    strategy actually exchanges — full replica for ``flat``, FSDP shard for
    ``hier``/``geococo``) or a gradient pytree of *logical* leaves, in
    which case the per-leaf accounting (``min_leaf_size`` dense fallback,
    chunk-granular top-k) matches :func:`sync_gradients`.  When leaves are
    split across in-pod devices, pass ``shard_factor`` (devices per leaf):
    the filter operates on the shard each device actually holds, so the
    dense-fallback threshold applies to ``leaf.size / shard_factor``, not
    the logical size.

    The exchange volume model is the ring all-reduce ``2 (P-1)/P`` factor;
    filtered values pay ``bytes_per_value + 4`` for the chunk-local index.
    The benchmarks cross-check this model against the WAN simulator's
    hierarchical schedule and against bytes actually moved on the mesh.
    """
    if n_pods <= 1:
        return 0.0
    spec = cfg.spec
    if isinstance(n_params, (int, float)):
        sizes = [float(n_params)]
    else:
        sizes = [float(l.size) for l in jax.tree.leaves(n_params)]
    dense = sparse = 0.0
    for n in sizes:
        d, s = spec.wire_values(n, cfg, shard_factor)
        dense += d
        sparse += s
    ring = 2.0 * (n_pods - 1) / n_pods
    return ring * (
        dense * bytes_per_value + sparse * (bytes_per_value + _INDEX_BYTES)
    )
