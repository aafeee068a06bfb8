"""Mixture-of-Experts FFN with top-k routing (+ shared experts).

Sort-free capacity dispatch: tokens pick top-k experts; within each expert
the first ``capacity`` tokens (by position-in-expert rank) are kept, the rest
drop (standard GShard/Switch semantics).  Dispatch and combine are expressed
as gather/scatter so compiled FLOPs reflect *active* expert compute
(tokens x k), not dense all-expert compute — this is what makes the MoE
roofline numbers honest.

Expert weights are stacked (E, d, d_ff) so expert parallelism is a plain
sharding annotation on the leading axis.  A layer may hold fewer experts
than its router is wide (``MoEConfig.n_held``): the first share of an
expert-parallel deployment, whose capacity and positions are still reckoned
over every expert.  Both paths route through :func:`route`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import MoEConfig
from .layers import Params, dense_apply, dense_init

__all__ = ["moe_init", "moe_apply", "route", "bias_step"]


def moe_init(key, d_model: int, moe: MoEConfig, *, dtype=jnp.float32) -> Params:
    """Router over ``moe.n_experts``, weights of the ``moe.held`` experts, a
    zero selection bias where the config has a bias rule, shared experts."""
    kr, ki, kg, ko, ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 0.02 / math.sqrt(2)
    e, f = moe.held, moe.d_expert
    p = {
        "router": dense_init(kr, d_model, moe.n_experts, scale=0.02, dtype=dtype),
        "wi": jax.random.normal(ki, (e, d_model, f), dtype) * scale_in,
        "wg": jax.random.normal(kg, (e, d_model, f), dtype) * scale_in,
        "wo": jax.random.normal(ko, (e, f, d_model), dtype) * scale_out,
    }
    if moe.bias_gamma > 0:
        p["bias"] = jnp.zeros((moe.n_experts,), dtype)
    if moe.n_shared > 0:
        d_sh = moe.shared_hidden * moe.n_shared
        k1, k2, k3 = jax.random.split(ks, 3)
        p["shared"] = {
            "wi": dense_init(k1, d_model, d_sh, dtype=dtype),
            "wg": dense_init(k2, d_model, d_sh, dtype=dtype),
            "wo": dense_init(k3, d_sh, d_model, scale=scale_out, dtype=dtype),
        }
    return p


def moe_apply(p: Params, x: jnp.ndarray, moe: MoEConfig):
    """Top-k MoE on ``x`` (B, S, d).  Returns ``(out, aux)``; ``aux`` is
    ``None`` unless ``moe.tracks_load`` (see :func:`route`).

    Under a distribution context with a model axis above 1, dispatch runs
    expert-parallel inside a manual shard_map (each device computes only
    its local experts; partial outputs psum over `model`) — both for
    performance and because XLA's SPMD scatter partitioner cannot be
    trusted with sharded dispatch on CPU."""
    from ..dist import context as dist_context

    ctx = dist_context.current()
    if ctx is not None and ctx.model_size > 1:
        return _moe_apply_manual_ep(p, x, moe, ctx=ctx), None
    return _moe_apply_dense_dispatch(p, x, moe)


def route(p: Params, xf: jnp.ndarray, moe: MoEConfig, *, seqs: int = 1,
          lo=0, hi=None):
    """Top-k routing of the tokens ``xf`` (T, d), ``seqs`` sequences of
    equal length, over the router's ``E`` experts.

    Returns ``(gate, expert, pos, keep, capacity, aux)``, the first four
    (T, K): each assignment's gate (normalised over the k chosen, times
    ``routed_scale``), its expert's index within the held range ``[lo,
    hi)`` (default: every expert), its position in that expert's buffer
    (clamped), and whether it is held and among the first ``capacity``
    assignments of its expert in (token, k) order over all ``E``.

    ``aux`` (``moe.tracks_load`` only) holds ``load``, each expert's
    assignments before capacity (E,); ``held`` and ``kept``, the held
    assignments and those capacity kept; ``balance``, the sequence-wise
    balance loss ``alpha * sum_i f_i P_i`` averaged over sequences
    (arXiv 2412.19437 eq. 17-20), ``f_i`` from the experts each token is
    routed to.  Only ``balance`` carries a gradient, through ``P_i``."""
    t = xf.shape[0]
    e = p["router"]["w"].shape[1]
    k = moe.top_k
    f32 = jnp.float32
    with jax.named_scope("moe_router"):
        logits = (xf.astype(f32) @ p["router"]["w"].astype(f32)).astype(f32)
        if moe.scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)                 # (T, E)
            gate, idx = jax.lax.top_k(scores, k)                     # (T, K)
        else:
            scores = jax.nn.sigmoid(logits)
            sel = scores + p["bias"].astype(f32) if "bias" in p else scores
            _, idx = jax.lax.top_k(sel, k)
            gate = jnp.take_along_axis(scores, idx, axis=-1)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        if moe.routed_scale != 1.0:
            gate = gate * moe.routed_scale

        capacity = max(1, int(moe.capacity_factor * k * t / e))
        # position of each (token, k) assignment within its expert's queue
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)            # (T, K, E)
        flat_oh = onehot.reshape(t * k, e)
        pos_in_expert = jnp.cumsum(flat_oh, axis=0) * flat_oh       # rank+1 where assigned
        pos = (pos_in_expert.max(axis=-1) - 1).reshape(t, k)        # (T, K)
        keep = pos < capacity
        if hi is None and lo == 0:
            expert, held = idx, None
        else:
            held = (idx >= lo) & (idx < hi)
            keep = keep & held
            expert = jnp.where(held, idx - lo, 0)
        pos = jnp.where(keep, pos, capacity - 1)                    # clamp; masked by keep

        aux = None
        if moe.tracks_load:
            counts = onehot.reshape(seqs, t // seqs * k, e).sum(1).astype(f32)  # (seqs, E)
            f = counts * (e / (k * (t // seqs)))
            share = scores / scores.sum(-1, keepdims=True)
            p_mean = share.reshape(seqs, t // seqs, e).mean(1)
            aux = {"load": counts.sum(0),
                   "held": jnp.asarray(t * k if held is None else held.sum(), f32),
                   "kept": keep.sum().astype(f32),
                   "balance": moe.balance_alpha * (f * p_mean).sum(-1).mean()}
    return gate, expert, pos, keep, capacity, aux


def bias_step(bias: jnp.ndarray, load: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """The selection bias after one step: each expert's moves by ``gamma``
    towards the mean load, ``gamma * sign(mean load - load)``."""
    return bias + gamma * jnp.sign(load.mean(-1, keepdims=True) - load).astype(bias.dtype)


def _moe_apply_manual_ep(p: Params, x: jnp.ndarray, moe: MoEConfig, *, ctx):
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

    if moe.tracks_load:
        raise NotImplementedError(
            "loads and the balance term of an expert-parallel layer need a sum "
            "over the token shards; no path runs it")
    b, s, d = x.shape
    e_held = p["wi"].shape[0]
    t = b * s
    top_k = moe.top_k
    dm = ctx.model_size
    dd = ctx.data_size
    # pad the held experts to a multiple of the model axis (dummy experts
    # hold zero weights and are never routed to: routing keeps indices
    # under the held count)
    e_pad = -(-e_held // dm) * dm
    e_local = e_pad // dm
    shard_tokens = dd > 1 and t % dd == 0
    fsdp_w = dd > 1 and d % dd == 0
    compute_dtype = x.dtype
    f32 = jnp.float32

    def pad_experts(w):
        if e_pad == e_held:
            return w
        return jnp.pad(w, ((0, e_pad - e_held), (0, 0), (0, 0)))

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

        gate_vals, le, pos_c, keep_l, capacity, _ = route(
            {"router": {"w": router_w}}, xf32, moe,
            lo=lo, hi=jnp.minimum(lo + e_local, e_held))

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
        with jax.named_scope("moe_experts"), jax.named_scope("moe_shared"):
            out = out + _shared_experts(p["shared"], xf)
    return out.reshape(b, s, d)


def _shared_experts(sh: Params, xf: jnp.ndarray) -> jnp.ndarray:
    hs = jax.nn.silu(dense_apply(sh["wg"], xf)) * dense_apply(sh["wi"], xf)
    return dense_apply(sh["wo"], hs)


def _moe_apply_dense_dispatch(p: Params, x: jnp.ndarray, moe: MoEConfig, *, lo: int = 0):
    """One device's experts: those held, ``p["wi"].shape[0]`` of them from
    expert ``lo`` of the router (the layer's share of an expert-parallel
    deployment).  Returns ``(out, aux)``."""
    b, s, d = x.shape
    e_held = p["wi"].shape[0]
    top_k = moe.top_k
    t = b * s
    xf = x.reshape(t, d)
    whole = lo == 0 and e_held == p["router"]["w"].shape[1]
    gate_vals, expert_idx, pos, keep, capacity, aux = route(
        p, xf, moe, seqs=b, lo=lo, hi=None if whole else lo + e_held)

    # dispatch: scatter token vectors into (E, C, d) buffers
    with jax.named_scope("moe_dispatch"):
        buf = jnp.zeros((e_held, capacity, d), xf.dtype)
        flat_e = expert_idx.reshape(-1)
        flat_pos = pos.reshape(-1)
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
        with jax.named_scope("moe_experts"), jax.named_scope("moe_shared"):
            out = out + _shared_experts(p["shared"], xf)

    return out.reshape(b, s, d), aux
