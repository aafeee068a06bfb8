"""Train / serve step builders: model + sync strategy + optimizer, sharded.

The step is built once per (arch, shape, mesh, strategy) cell.  Model
compute always runs under GSPMD (``jax.jit`` + sharding constraints): FSDP
over ``data`` and tensor parallelism over ``model`` inside a pod.  The pod
(WAN-analogue) boundary is owned by the GeoCoCo communicator: the gradient
exchange runs in a ``shard_map`` that is manual over ``pod`` only (the
``data`` / ``model`` axes stay with GSPMD inside it), where
``repro.dist.collectives.sync_gradients`` resolves the configured strategy
through the two-plane registry.  This split — GSPMD inside the pod, an
explicit collective program across pods — mirrors the paper's architecture
(intra-group transfers are cheap and automatic; the inter-group exchange is
planned).

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input, so
the multi-pod dry-run lowers and compiles with zero allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeSpec
from ..dist.collectives import SyncConfig, sync_gradients
from ..dist.sharding import param_shardings, param_specs
from ..models.model import forward, init_cache, init_params
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update

__all__ = [
    "TrainConfig",
    "input_specs",
    "abstract_params",
    "abstract_opt_state",
    "abstract_residuals",
    "abstract_cache",
    "build_train_step",
    "build_serve_step",
    "loss_fn",
]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    sync: SyncConfig = SyncConfig()
    optim: AdamWConfig = AdamWConfig()
    param_dtype: Any = jnp.float32      # bf16 for the lean 671B policy
    compute_dtype: Any = jnp.bfloat16
    # gradient-accumulation microbatches: activation memory scales ~1/m and
    # gradients sync once per step (GeoCoCo semantics unchanged)
    microbatches: int = 1


# ---------------------------------------------------------------------------
# abstract inputs (dry-run stand-ins)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs for the model inputs of one cell."""
    gb, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        toks = {"tokens": jax.ShapeDtypeStruct((gb, 1), jnp.int32)}
        if cfg.n_img_tokens:
            toks["img"] = jax.ShapeDtypeStruct(
                (gb, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16
            )
        return toks
    batch: dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.frontend == "token":
        batch["tokens"] = jax.ShapeDtypeStruct((gb, s), jnp.int32)
    else:
        batch["embeds"] = jax.ShapeDtypeStruct((gb, s, cfg.d_model), jnp.bfloat16)
    if cfg.n_img_tokens:
        batch["img"] = jax.ShapeDtypeStruct(
            (gb, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16
        )
    if shape.kind == "train":
        batch["labels"] = jax.ShapeDtypeStruct((gb, s), jnp.int32)
    return batch


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, dtype), shapes)


def abstract_opt_state(cfg: ModelConfig, tcfg: TrainConfig):
    params = abstract_params(cfg, tcfg.param_dtype)
    return jax.eval_shape(lambda p: adamw_init(p, tcfg.optim), params)


def abstract_residuals(cfg: ModelConfig, tcfg: TrainConfig):
    if not tcfg.sync.needs_residuals:
        return None
    params = abstract_params(cfg, tcfg.param_dtype)
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), params)


def abstract_cache(cfg: ModelConfig, shape: ShapeSpec, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len, dtype)
    )


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def _fit_batch_axes(mesh: Mesh, dim: int) -> tuple[str, ...]:
    """Largest prefix-combination of (pod, data) that divides ``dim``.

    A global_batch of 1 (long_500k single-request decode) replicates over the
    batch axes; the model axis still shards the compute."""
    cands = [("pod", "data"), ("data",), ("pod",)]
    for axes in cands:
        if all(a in mesh.shape for a in axes):
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if size > 1 and dim % size == 0:
                return axes
    return ()


def _batch_shardings(batch_tree, mesh: Mesh):
    def one(l):
        if getattr(l, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        axes = _fit_batch_axes(mesh, l.shape[0])
        return NamedSharding(mesh, P(axes or None, *([None] * (l.ndim - 1))))

    return jax.tree.map(one, batch_tree)


def _is_scan_path(path) -> bool:
    for p in path:
        if getattr(p, "key", None) == "scan":
            return True
    return False


def _cache_shardings(cache_tree, mesh: Mesh):
    """Decode-cache shardings.  Leaves under the "scan" key are stacked with
    a leading super-block axis: their batch dim is axis 1, not 0."""
    dm = mesh.shape.get("model", 1)

    def one(path, l):
        off = 1 if _is_scan_path(path) else 0
        if l.ndim <= off:
            return NamedSharding(mesh, P())
        spec = [None] * l.ndim
        spec[off] = _fit_batch_axes(mesh, l.shape[off]) or None
        # shard the sequence/time dim over `model` when long and divisible:
        # sequence-parallel KV caches keep 32k decode in HBM.  Short
        # (ring-buffer window) caches stay unsharded — small, and their
        # rotation gathers would hit the partitioner.
        sdim = off + 1
        if (
            l.ndim > sdim
            and l.shape[sdim] % dm == 0
            and l.shape[sdim] >= 8192
            and dm > 1
        ):
            spec[sdim] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def _constrain(tree, shardings):
    return jax.tree.map(
        lambda x, ns: jax.lax.with_sharding_constraint(x, ns), tree, shardings
    )


def _constrain_batch(batch, mesh: Mesh):
    """Pin the batch dim over the (pod, data) device axes inside the step."""

    def one(x):
        if getattr(x, "ndim", 0) == 0:
            return x
        axes = _fit_batch_axes(mesh, x.shape[0])
        if not axes:
            return x
        spec = P(axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree.map(one, batch)


def _act_constrain(mesh: Mesh, *, seq_parallel: bool = False):
    """Residual-stream constraint at block boundaries.

    Batch over `data` (so GSPMD never resolves an FSDP weight/activation
    conflict by replicating the batch).  ``seq_parallel`` additionally shards
    the sequence dim over `model` (Megatron-style) — measured on this
    container it triggers GSPMD resharding storms under the FSDP weight
    gathers, so it stays off by default.
    """
    dd = mesh.shape.get("data", 1)
    dm = mesh.shape.get("model", 1)
    dp = mesh.shape.get("pod", 1)
    if dd <= 1 and dm <= 1 and dp <= 1:
        return None
    baxes = [a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1]
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]

    def ac(x):
        if x.ndim < 2:
            return x
        spec = [None] * x.ndim
        if baxes and x.shape[0] % bsize == 0:
            spec[0] = tuple(baxes)
        if (
            seq_parallel
            and dm > 1
            and x.ndim >= 3
            and x.shape[1] % dm == 0
        ):
            spec[1] = "model"
        if not any(spec):
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec))
        )

    return ac


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_fn(cfg: ModelConfig, params, batch, compute_dtype=jnp.bfloat16,
            act_constrain=None):
    logits, _ = forward(cfg, params, batch, compute_dtype=compute_dtype,
                        act_constrain=act_constrain)
    labels = batch["labels"]
    with jax.named_scope("logits"):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0].mean()


# ---------------------------------------------------------------------------
# pod-boundary gradient sync (shard_map manual over `pod`)
# ---------------------------------------------------------------------------


def _strip_auto_axes(spec: P) -> P:
    """Drop non-``pod`` mesh axes from a spec.

    In a shard_map manual over ``pod`` only, the in/out specs may mention
    only the manual axis — ``data`` / ``model`` sharding stays with GSPMD.
    """
    out = []
    for part in spec:
        if part is None:
            out.append(None)
            continue
        parts = part if isinstance(part, tuple) else (part,)
        kept = tuple(a for a in parts if a == "pod")
        out.append(kept[0] if len(kept) == 1 else (kept or None))
    return P(*out)


def _make_pod_sync(mesh: Mesh, tcfg: TrainConfig, p_spec, *, with_residuals: bool):
    """Wrap ``sync_gradients`` in a shard_map over the pod axis.

    Gradients enter at their parameter partitioning (``p_spec``); each
    device holds its FSDP/TP shard and exchanges it across the ``pod`` axis
    under the configured strategy.  Residual state (geococo error feedback)
    is carried at the same partitioning.  Only the pod components of the
    specs survive; GSPMD keeps the in-pod partitioning.
    """
    n_pods = mesh.shape.get("pod", 1)
    p_spec = jax.tree.map(_strip_auto_axes, p_spec)

    if with_residuals:

        def body(g, r):
            with jax.named_scope("pod_exchange"):
                return sync_gradients(g, r, tcfg.sync, axis="pod", n_pods=n_pods)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_spec, p_spec), out_specs=(p_spec, p_spec),
            axis_names={"pod"}, check_vma=False,
        )

    def body(g):
        with jax.named_scope("pod_exchange"):
            return sync_gradients(g, None, tcfg.sync, axis="pod", n_pods=n_pods)[0]

    return jax.shard_map(
        body, mesh=mesh, in_specs=(p_spec,), out_specs=p_spec,
        axis_names={"pod"}, check_vma=False,
    )


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig):
    """Returns (make_jit, shardings dict).

    step(params, opt_state, residuals, batch) ->
        (params', opt_state', residuals', metrics)
    """
    n_pods = mesh.shape.get("pod", 1)
    p_abs = abstract_params(cfg, tcfg.param_dtype)
    p_spec = param_specs(p_abs, mesh, tcfg.sync.strategy)
    p_shard = param_shardings(p_abs, mesh, tcfg.sync.strategy)
    opt_shard = {
        "m": p_shard,
        "v": p_shard,
        "step": NamedSharding(mesh, P()),
    }
    res_abs = abstract_residuals(cfg, tcfg)
    res_shard = p_shard if res_abs is not None else None

    ac = _act_constrain(mesh) if tcfg.sync.strategy != "flat" else None
    n_micro = max(1, tcfg.microbatches)
    pod_sync = (
        _make_pod_sync(mesh, tcfg, p_spec,
                       with_residuals=res_abs is not None)
        if n_pods > 1
        else None
    )

    def core(params, opt_state, residuals, batch):
        from ..dist import context as dist_context

        params = _constrain(params, p_shard)
        with dist_context.distribution(mesh):
            if n_micro == 1:
                b = _constrain_batch(batch, mesh)
                loss, grads = jax.value_and_grad(
                    lambda p: loss_fn(cfg, p, b, tcfg.compute_dtype, ac)
                )(params)
            else:
                # gradient accumulation: one fwd/bwd per microbatch; only the
                # accumulated gradient crosses the pod boundary (per-step sync
                # frequency unchanged — the paper's epoch semantics)
                micro = jax.tree.map(
                    lambda x: x.reshape(
                        (n_micro, x.shape[0] // n_micro) + x.shape[1:]
                    ),
                    batch,
                )
                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )

                def mb_step(carry, mbatch):
                    gsum, lsum = carry
                    b = _constrain_batch(mbatch, mesh)
                    l, g = jax.value_and_grad(
                        lambda p: loss_fn(cfg, p, b, tcfg.compute_dtype, ac)
                    )(params)
                    gsum = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), gsum, g
                    )
                    return (gsum, lsum + l), None

                (gsum, lsum), _ = jax.lax.scan(
                    mb_step, (g0, jnp.zeros((), jnp.float32)), micro
                )
                grads = jax.tree.map(
                    lambda g, p: (g / n_micro).astype(p.dtype), gsum, params
                )
                loss = lsum / n_micro
        new_res = residuals
        if pod_sync is not None:
            if res_abs is not None:
                grads, new_res = pod_sync(grads, residuals)
            else:
                grads = pod_sync(grads)
        new_params, new_opt, metrics = adamw_update(
            params, grads, opt_state, tcfg.optim
        )
        new_params = _constrain(new_params, p_shard)
        metrics = dict(metrics, loss=loss)
        return new_params, new_opt, new_res, metrics

    def make_jit(batch_tree):
        b_shard = _batch_shardings(batch_tree, mesh)
        in_sh = (p_shard, opt_shard, res_shard, b_shard)
        out_sh = (p_shard, opt_shard, res_shard, None)
        return jax.jit(
            core,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=(0, 1, 2),
        )

    shardings = {"params": p_shard, "opt": opt_shard, "residuals": res_shard}
    return make_jit, shardings


def build_serve_step(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig,
                     *, kind: str = "decode"):
    """Prefill: step(params, batch) -> logits.
    Decode: step(params, cache, batch) -> (next_tokens, new_cache)."""
    p_abs = abstract_params(cfg, tcfg.param_dtype)
    p_shard = param_shardings(p_abs, mesh, tcfg.sync.strategy)

    if kind == "prefill":
        ac = _act_constrain(mesh) if tcfg.sync.strategy != "flat" else None

        def core(params, batch):
            from ..dist import context as dist_context

            params = _constrain(params, p_shard)
            batch = _constrain_batch(batch, mesh)
            with dist_context.distribution(mesh):
                logits, _ = forward(cfg, params, batch,
                                    compute_dtype=tcfg.compute_dtype,
                                    act_constrain=ac)
            return logits

        def make_jit(batch_tree):
            b_shard = _batch_shardings(batch_tree, mesh)
            return jax.jit(core, in_shardings=(p_shard, b_shard))

        return make_jit, {"params": p_shard}

    ac_dec = _act_constrain(mesh) if tcfg.sync.strategy != "flat" else None

    def core(params, cache, batch):
        from ..dist import context as dist_context

        params = _constrain(params, p_shard)
        batch = _constrain_batch(batch, mesh)
        cache = _constrain(cache, _cache_shardings(cache, mesh))
        with dist_context.distribution(mesh):
            logits, new_cache = forward(
                cfg, params, batch, cache=cache,
                compute_dtype=tcfg.compute_dtype,
                act_constrain=ac_dec,
            )
        next_tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return next_tok.astype(jnp.int32), new_cache

    def make_jit(cache_tree, batch_tree):
        c_shard = _cache_shardings(cache_tree, mesh)
        b_shard = _batch_shardings(batch_tree, mesh)
        gb = next(iter(jax.tree.leaves(batch_tree))).shape[0]
        tok_shard = NamedSharding(mesh, P(_fit_batch_axes(mesh, gb) or None))
        return jax.jit(
            core,
            in_shardings=(p_shard, c_shard, b_shard),
            out_shardings=(tok_shard, c_shard),
            donate_argnums=(1,),
        )

    return make_jit, {"params": p_shard}
