"""Train / serve step builders: model + sync strategy + optimizer, sharded.

The step is built once per (arch, shape, mesh, strategy) cell.  Model
compute always runs under GSPMD (``jax.jit`` + sharding constraints): FSDP
over ``data`` and tensor parallelism over ``model`` inside a pod.  The pod
(WAN-analogue) boundary is owned by the GeoCoCo communicator.  With more
than one pod, the forward and backward passes run in a ``shard_map`` that
is manual over ``pod`` only (the ``data`` / ``model`` axes stay with GSPMD
inside it): each pod takes the loss and gradient of its own share of the
batch, as a region computes on its own sequences, and
``repro.dist.collectives.sync_gradients`` then exchanges them under the
configured strategy, resolved through the two-plane registry, each chip
on its own shard of a leaf wherever that gives the whole leaf's rows.  That
exchange and the scalar loss mean are the only traffic over ``pod``.  This
split — GSPMD inside the pod, an explicit collective program across pods —
mirrors the paper's architecture (intra-group transfers are cheap and
automatic; the inter-group exchange is planned).

Error-feedback residuals (``geococo``) differ by pod: with ``n_pods > 1``
each leaf carries a leading axis of size ``n_pods`` sharded over ``pod``.

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
from ..dist.collectives import SyncConfig, shard_local_specs, sync_gradients
from ..dist.sharding import param_shardings, param_specs
from ..models.model import (forward, forward_with_aux, init_cache, init_params, moe_records,
                            with_bias_step)
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


def abstract_residuals(cfg: ModelConfig, tcfg: TrainConfig, n_pods: int = 1):
    """Error-feedback state, ``None`` for a strategy without it.  Each pod
    keeps its own: with ``n_pods > 1`` every leaf gains a leading pod axis."""
    if not tcfg.sync.needs_residuals:
        return None
    lead = (n_pods,) if n_pods > 1 else ()
    params = abstract_params(cfg, tcfg.param_dtype)
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(lead + l.shape, jnp.float32), params
    )


def abstract_cache(cfg: ModelConfig, shape: ShapeSpec, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len, dtype)
    )


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def _fit_batch_axes(mesh: Mesh, dim: int, *, over_pod: bool = True) -> tuple[str, ...]:
    """Largest prefix-combination of (pod, data) that divides ``dim``.

    A global_batch of 1 (long_500k single-request decode) replicates over the
    batch axes; the model axis still shards the compute.  ``over_pod=False``
    leaves ``pod`` out, as inside the pod region, where it is manual."""
    cands = [("pod", "data"), ("data",), ("pod",)] if over_pod else [("data",)]
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


def _constrain_batch(batch, mesh: Mesh, *, over_pod: bool = True):
    """Pin the batch dim over the (pod, data) device axes inside the step
    (over ``data`` alone with ``over_pod=False``)."""

    def one(x):
        if getattr(x, "ndim", 0) == 0:
            return x
        axes = _fit_batch_axes(mesh, x.shape[0], over_pod=over_pod)
        if not axes:
            return x
        spec = P(axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree.map(one, batch)


def _act_constrain(mesh: Mesh, *, seq_parallel: bool = False, over_pod: bool = True):
    """Residual-stream constraint at block boundaries.

    Batch over `data` (so GSPMD never resolves an FSDP weight/activation
    conflict by replicating the batch), and over `pod` unless
    ``over_pod=False`` (inside the pod region).  ``seq_parallel``
    additionally shards the sequence dim over `model` (Megatron-style) —
    measured on this container it triggers GSPMD resharding storms under the
    FSDP weight gathers, so it stays off by default.
    """
    dd = mesh.shape.get("data", 1)
    dm = mesh.shape.get("model", 1)
    dp = mesh.shape.get("pod", 1) if over_pod else 1
    if dd <= 1 and dm <= 1 and dp <= 1:
        return None
    baxes = [a for a in (("pod", "data") if over_pod else ("data",))
             if mesh.shape.get(a, 1) > 1]
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
            act_constrain=None, *, embed_fn=None, logp_constrain=None):
    """``(loss, aux)``: the mean next-token cross-entropy plus every MoE
    layer's balance term, and the routing records (``forward_with_aux``).
    ``embed_fn`` replaces the vocabulary lookup (see ``forward``);
    ``logp_constrain`` pins the log-probabilities before the label pick."""
    logits, aux = forward_with_aux(cfg, params, batch, compute_dtype=compute_dtype,
                                   act_constrain=act_constrain, embed_fn=embed_fn)
    labels = batch["labels"]
    with jax.named_scope("logits"):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        if logp_constrain is not None:
            lp = logp_constrain(lp)
        loss = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0].mean()
    records = moe_records(aux)
    if records:
        loss = loss + sum(r["balance"].sum() for r in records)
    return loss, aux


def routing_counters(aux) -> dict:
    """``moe_load_peak``: the most any expert of any MoE layer was routed,
    over its layer's mean load, before capacity; ``moe_drop_share``: the
    share of the held assignments that capacity dropped.  Empty for a
    model whose layers record no routing."""
    records = moe_records(aux)
    if not records:
        return {}
    peak = max(jnp.max(r["load"] / r["load"].mean(-1, keepdims=True)) for r in records)
    held = sum(r["held"].sum() for r in records)
    kept = sum(r["kept"].sum() for r in records)
    return {"moe_load_peak": peak, "moe_drop_share": 1.0 - kept / held}


def _without_bias(grads):
    """The gradient with each router's selection bias left out (``None``):
    the bias moves by its own rule, not by the optimizer."""
    return jax.tree_util.tree_map_with_path(
        lambda path, g: None if [getattr(k, "key", None) for k in path[-2:]]
        == ["ffn", "bias"] else g, grads)


# ---------------------------------------------------------------------------
# the pod region (shard_map manual over `pod`)
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


def _whole_vocab_lookups(mesh: Mesh, ac) -> dict:
    """``loss_fn`` arguments that keep the vocabulary whole at its two
    gathers (the embedding lookup, the label pick), for the pod region.

    XLA's SPMD partitioner aborts (``ExpandDeviceGroupsWithIota``) when it
    partitions a gather along a sharded operand dimension inside a region
    manual over ``pod`` (seen with ``model`` > 1), so there the table is
    gathered whole before the lookup and the log-probabilities keep only
    their batch dimension split."""
    whole = NamedSharding(mesh, P())

    def embed(p, tokens, dtype):
        table = jax.lax.with_sharding_constraint(p["table"].astype(dtype), whole)
        return table[tokens]

    return {"embed_fn": embed, "logp_constrain": ac}


def _make_pod_step(mesh: Mesh, tcfg: TrainConfig, p_spec, plan, loss_and_grads):
    """Each pod's loss and gradient, then the exchange across pods.

    ``loss_and_grads(params, batch)`` runs inside a ``shard_map`` manual
    over ``pod``: it sees its pod's own rows of the batch and the parameters
    at their in-pod partitioning (only the pod components of ``p_spec``
    survive; GSPMD keeps FSDP/TP), so nothing crosses ``pod`` before
    ``sync_gradients`` exchanges the gradients under the configured
    strategy, in the ``pod_exchange`` scope.  The exchange runs in a
    ``shard_map`` nested manual over the in-pod axes, on each leaf as
    ``plan`` (``shard_local_specs``) gives it: the chip's own shard, or the
    whole leaf.  Residuals carry a leading pod axis, one slice per pod.
    Returns ``step(params, batch, residuals) -> (loss mean over pods,
    synced grads, new residuals)``.
    """
    n_pods = mesh.shape["pod"]
    g_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec)
    p_spec = jax.tree.map(_strip_auto_axes, p_spec)
    in_pod = {a for a in mesh.axis_names if a != "pod"}

    def exchange(grads, res):
        return sync_gradients(grads, res, tcfg.sync, axis="pod", n_pods=n_pods)

    def body(params, batch, residuals):
        loss, grads = loss_and_grads(params, batch)
        # the whole backward pass before any of the exchange: left free, the
        # TPU scheduler overlaps the exchange's temporaries with it, and the
        # 8-layer granite step on pod=2 x data=2 with whole-leaf exchanges
        # needed 15.81 GiB of a v5e's 15.75 (15.02 GiB with the barrier).
        # The gradients leave it at their parameters' layout, whatever
        # layout the exchange takes them in, so the plan cannot change how
        # GSPMD sums them (a tied embedding's two parts, say)
        grads = jax.lax.optimization_barrier(_constrain(grads, g_shard))
        res = None if residuals is None else jax.tree.map(lambda r: r[0], residuals)
        specs = (plan, None if res is None else plan)
        with jax.named_scope("pod_exchange"):
            grads, res = jax.shard_map(
                exchange, mesh=jax.sharding.get_abstract_mesh(), in_specs=specs,
                out_specs=specs, axis_names=in_pod, check_vma=False,
            )(grads, res)
        if res is not None:
            res = jax.tree.map(lambda r: r[None], res)
        return jax.lax.pmean(loss, "pod"), grads, res

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_spec, P("pod"), P("pod")), out_specs=(P(), p_spec, P("pod")),
        axis_names={"pod"}, check_vma=False,
    )


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, mesh: Mesh, tcfg: TrainConfig):
    """Returns (make_jit, shardings dict).

    step(params, opt_state, residuals, batch) ->
        (params', opt_state', residuals', metrics)

    With more than one pod each pod's gradient comes from its own rows of
    the batch and crosses ``pod`` only through the exchange
    (:func:`_make_pod_step`); the loss reported is the mean over pods.  The
    dict also holds ``"exchange"``: the in-pod spec each gradient leaf
    enters the exchange with (``shard_local_specs``), ``None`` at one pod.
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
    res_shard = None
    if tcfg.sync.needs_residuals:
        res_shard = p_shard if n_pods == 1 else jax.tree.map(
            lambda s: NamedSharding(mesh, P("pod", *s)), p_spec)

    # in the pod region `pod` is manual, so no constraint there may name it
    over_pod = n_pods == 1
    ac = (_act_constrain(mesh, over_pod=over_pod)
          if tcfg.sync.strategy != "flat" else None)
    lookups = {} if over_pod else _whole_vocab_lookups(mesh, ac)
    n_micro = max(1, tcfg.microbatches)

    def loss(p, b):
        return loss_fn(cfg, p, b, tcfg.compute_dtype, ac, **lookups)

    def loss_and_grads(params, batch):
        """``((loss, routing records), grads)``."""
        if n_micro == 1:
            b = _constrain_batch(batch, mesh, over_pod=over_pod)
            return jax.value_and_grad(lambda p: loss(p, b), has_aux=True)(params)
        # gradient accumulation: one fwd/bwd per microbatch; only the
        # accumulated gradient crosses the pod boundary (per-step sync
        # frequency unchanged — the paper's epoch semantics)
        micro = jax.tree.map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
            batch,
        )
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def mb_step(carry, mbatch):
            gsum, lsum = carry
            b = _constrain_batch(mbatch, mesh, over_pod=over_pod)
            (l, aux), g = jax.value_and_grad(lambda p: loss(p, b), has_aux=True)(params)
            gsum = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), gsum, g)
            return (gsum, lsum + l), aux

        (gsum, lsum), auxs = jax.lax.scan(
            mb_step, (g0, jnp.zeros((), jnp.float32)), micro
        )
        grads = jax.tree.map(lambda g, p: (g / n_micro).astype(p.dtype), gsum, params)
        # the microbatches' records: loads and counts summed
        aux = jax.tree.map(lambda a: a.sum(0), auxs)
        return (lsum / n_micro, aux), grads

    bias_rule = cfg.moe is not None and cfg.moe.bias_gamma > 0
    plan = pod_step = None
    if n_pods > 1:
        if bias_rule:
            raise NotImplementedError(
                "a router's bias rule needs each pod's loads summed over `pod` "
                "outside the exchange; the pod step has no such path")
        plan = shard_local_specs(p_abs, p_spec, mesh.shape, tcfg.sync)

        def pod_loss_and_grads(params, batch):
            (loss, _), grads = loss_and_grads(params, batch)
            return loss, grads

        pod_step = _make_pod_step(mesh, tcfg, p_spec, plan, pod_loss_and_grads)

    def core(params, opt_state, residuals, batch):
        from ..dist import context as dist_context

        params = _constrain(params, p_shard)
        with dist_context.distribution(mesh):
            counters = {}
            if pod_step is None:
                (loss, aux), grads = loss_and_grads(params, batch)
                counters = routing_counters(aux)
                new_res = residuals
            else:
                loss, grads, new_res = pod_step(params, batch, residuals)
        if bias_rule:
            grads = _without_bias(grads)
        new_params, new_opt, metrics = adamw_update(
            params, grads, opt_state, tcfg.optim
        )
        if bias_rule:
            new_params = with_bias_step(cfg, new_params, aux)
        new_params = _constrain(new_params, p_shard)
        metrics = dict(metrics, loss=loss, **counters)
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

    shardings = {"params": p_shard, "opt": opt_shard, "residuals": res_shard,
                 "exchange": plan}
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
