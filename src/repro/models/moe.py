"""Mixture-of-Experts FFN with top-k routing (+ shared experts).

Sort-free capacity dispatch: tokens pick top-k experts; within each expert
the first ``capacity`` tokens (by position-in-expert rank) are kept, the rest
drop (standard GShard/Switch semantics).  Dispatch and combine are expressed
as gather/scatter so compiled FLOPs reflect *active* expert compute
(tokens x k), not dense all-expert compute — this is what makes the MoE
roofline numbers honest.

Expert weights are stacked (E, d, d_ff) so expert parallelism is a plain
sharding annotation on the leading axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import Params, dense_apply, dense_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(
    key,
    d_model: int,
    n_experts: int,
    d_expert: int,
    *,
    n_shared: int = 0,
    d_shared: int = 0,
    dtype=jnp.float32,
) -> Params:
    kr, ki, kg, ko, ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 0.02 / math.sqrt(2)
    p = {
        "router": dense_init(kr, d_model, n_experts, scale=0.02, dtype=dtype),
        "wi": jax.random.normal(ki, (n_experts, d_model, d_expert), dtype) * scale_in,
        "wg": jax.random.normal(kg, (n_experts, d_model, d_expert), dtype) * scale_in,
        "wo": jax.random.normal(ko, (n_experts, d_expert, d_model), dtype) * scale_out,
    }
    if n_shared > 0:
        d_sh = (d_shared or d_expert) * n_shared
        k1, k2, k3 = jax.random.split(ks, 3)
        p["shared"] = {
            "wi": dense_init(k1, d_model, d_sh, dtype=dtype),
            "wg": dense_init(k2, d_model, d_sh, dtype=dtype),
            "wo": dense_init(k3, d_sh, d_model, scale=scale_out, dtype=dtype),
        }
    return p


def moe_apply(
    p: Params,
    x: jnp.ndarray,                  # (B, S, d)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    return_aux: bool = False,
):
    """Top-k MoE.  Under a distribution context with a model axis that
    divides the expert count, dispatch runs expert-parallel inside a manual
    shard_map (each device computes only its local experts; partial outputs
    psum over `model`) — both for performance and because XLA's SPMD
    scatter partitioner cannot be trusted with sharded dispatch on CPU."""
    from ..dist import context as dist_context

    e = p["wi"].shape[0]
    ctx = dist_context.current()
    if not return_aux and ctx is not None and ctx.model_size > 1:
        return _moe_apply_manual_ep(p, x, top_k=top_k,
                                    capacity_factor=capacity_factor, ctx=ctx)
    return _moe_apply_dense_dispatch(
        p, x, top_k=top_k, capacity_factor=capacity_factor,
        return_aux=return_aux,
    )


def _moe_apply_manual_ep(p: Params, x: jnp.ndarray, *, top_k: int,
                         capacity_factor: float, ctx):
    """Expert parallelism: experts over `model`, tokens over `data`, expert
    weights FSDP'd over `data` and all-gathered per layer inside the manual
    region (the scan-over-layers keeps exactly one gather alive at a time).

    Every device routes its own token shard and computes only its model
    column's experts for those tokens; a psum over `model` assembles the
    per-token expert sums.  Dispatch uses top-k capacity buffers written by
    ``top_k`` scatters (never a (T*k, d) repeat).  All shard_map boundaries
    and psums are f32 (XLA's bf16 AllReducePromotion CHECK-fails on CPU).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e = p["wi"].shape[0]
    t = b * s
    dm = ctx.model_size
    dd = ctx.data_size
    # pad the expert dim to a multiple of the model axis (dummy experts hold
    # zero weights and are never routed to: the router has only `e` outputs)
    e_pad = -(-e // dm) * dm
    e_local = e_pad // dm
    shard_tokens = dd > 1 and t % dd == 0
    t_local = t // dd if shard_tokens else t
    capacity = max(1, int(capacity_factor * top_k * t_local / e))
    fsdp_w = dd > 1 and d % dd == 0
    compute_dtype = x.dtype
    f32 = jnp.float32

    def pad_experts(w):
        if e_pad == e:
            return w
        return jnp.pad(w, ((0, e_pad - e), (0, 0), (0, 0)))

    # per-shard expert offsets as a model-sharded iota (avoids axis_index,
    # whose lowering re-binds the outer manual pod axis)
    offsets = jnp.arange(dm, dtype=jnp.int32) * e_local

    def local_ep(xf32, router_w, wi, wg, wo, off):
        xf = xf32.astype(compute_dtype)          # (T_local, d)
        if fsdp_w:
            # FSDP gather of this layer's experts (f32 boundary keeps the
            # reduce-scatter cotangent f32)
            wi_ = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wg_ = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wo_ = jax.lax.all_gather(wo, "data", axis=1, tiled=True)
        else:
            wi_, wg_, wo_ = wi, wg, wo
        wi_ = wi_.astype(compute_dtype)
        wg_ = wg_.astype(compute_dtype)
        wo_ = wo_.astype(compute_dtype)
        lo = off[0]
        tl = xf.shape[0]

        with jax.named_scope("moe_router"):
            logits = (xf32 @ router_w).astype(f32)
            probs = jax.nn.softmax(logits, axis=-1)                  # (Tl, E)
            gate_vals, expert_idx = jax.lax.top_k(probs, top_k)      # (Tl, K)
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9
            )
            onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)
            pos_in_expert = (
                jnp.cumsum(onehot.reshape(tl * top_k, e), axis=0)
                * onehot.reshape(tl * top_k, e)
            )
            pos = (pos_in_expert.max(axis=-1) - 1).reshape(tl, top_k)
            keep = pos < capacity
            is_local = (expert_idx >= lo) & (expert_idx < lo + e_local)
            keep_l = keep & is_local
            le = jnp.where(is_local, expert_idx - lo, 0)             # (Tl, K)
            pos_c = jnp.where(keep_l, pos, capacity - 1)

        with jax.named_scope("moe_dispatch"):
            buf = jnp.zeros((e_local, capacity, d), compute_dtype)
            for j in range(top_k):  # top_k scatters — no (T*k, d) repeat
                src = xf * keep_l[:, j, None].astype(compute_dtype)
                buf = buf.at[le[:, j], pos_c[:, j]].add(src)
        with jax.named_scope("moe_experts"):
            h = jnp.einsum("ecd,edf->ecf", buf, wi_)
            g = jnp.einsum("ecd,edf->ecf", buf, wg_)
            y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo_)

        with jax.named_scope("moe_combine"):
            out = jnp.zeros((tl, d), f32)
            for j in range(top_k):
                got = y[le[:, j], pos_c[:, j]].astype(f32)
                w_j = (gate_vals[:, j] * keep_l[:, j]).astype(f32)
                out = out + got * w_j[:, None]
            return jax.lax.psum(out, "model")

    manual = {"model"} | ({"data"} if (shard_tokens or fsdp_w) else set())
    tspec = P("data") if shard_tokens else P()
    wspec = P("model", "data") if fsdp_w else P("model")
    xf = x.reshape(t, d)
    sm = ctx.shard_map(
        local_ep,
        in_specs=(tspec, P(), wspec, wspec, wspec, P("model")),
        out_specs=tspec,
        axis_names=manual,
    )
    out = sm(
        xf.astype(f32),
        p["router"]["w"].astype(f32),
        pad_experts(p["wi"]).astype(f32),
        pad_experts(p["wg"]).astype(f32),
        pad_experts(p["wo"]).astype(f32),
        offsets,
    ).astype(x.dtype)

    if "shared" in p:
        with jax.named_scope("moe_experts"):
            out = out + _shared_experts(p["shared"], xf)
    return out.reshape(b, s, d)


def _shared_experts(sh: Params, xf: jnp.ndarray) -> jnp.ndarray:
    hs = jax.nn.silu(dense_apply(sh["wg"], xf)) * dense_apply(sh["wi"], xf)
    return dense_apply(sh["wo"], hs)


def _moe_apply_dense_dispatch(
    p: Params,
    x: jnp.ndarray,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    return_aux: bool = False,
):
    b, s, d = x.shape
    e = p["wi"].shape[0]
    t = b * s
    xf = x.reshape(t, d)

    with jax.named_scope("moe_router"):
        logits = (xf @ p["router"]["w"].astype(jnp.float32)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
        gate_vals, expert_idx = jax.lax.top_k(probs, top_k)         # (T, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        capacity = max(1, int(capacity_factor * top_k * t / e))
        # position of each (token, k) assignment within its expert's queue
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)     # (T, K, E)
        flat_oh = onehot.reshape(t * top_k, e)
        pos_in_expert = jnp.cumsum(flat_oh, axis=0) * flat_oh       # rank+1 where assigned
        pos = (pos_in_expert.max(axis=-1) - 1).reshape(t, top_k)    # (T, K)
        keep = pos < capacity

    # dispatch: scatter token vectors into (E, C, d) buffers
    with jax.named_scope("moe_dispatch"):
        buf = jnp.zeros((e, capacity, d), xf.dtype)
        flat_e = expert_idx.reshape(-1)
        flat_pos = jnp.where(keep, pos, capacity - 1).reshape(-1)   # clamp; masked below
        flat_keep = keep.reshape(-1)
        src = jnp.repeat(xf, top_k, axis=0) * flat_keep[:, None].astype(xf.dtype)
        buf = buf.at[flat_e, flat_pos].add(src)

    # expert FFN: (E, C, d) x (E, d, f)
    with jax.named_scope("moe_experts"):
        h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(xf.dtype))
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(xf.dtype))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"].astype(xf.dtype))

    # combine: gather each assignment's output, weight by gate
    with jax.named_scope("moe_combine"):
        out_tok = y[flat_e, flat_pos]                               # (T*K, d)
        out_tok = out_tok * (gate_vals.reshape(-1) * flat_keep).astype(xf.dtype)[:, None]
        out = out_tok.reshape(t, top_k, d).sum(axis=1)

    if "shared" in p:
        with jax.named_scope("moe_experts"):
            out = out + _shared_experts(p["shared"], xf)

    out = out.reshape(b, s, d)
    if not return_aux:
        return out
    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    frac_tokens = onehot.astype(jnp.float32).sum(axis=(0, 1)) / (t * top_k)
    frac_probs = probs.mean(axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    dropped = 1.0 - flat_keep.astype(jnp.float32).mean()
    return out, {"aux_loss": aux, "drop_rate": dropped}
