"""Shared model primitives (pure JAX, functional, explicit param pytrees).

Conventions:
* params are nested dicts of jnp arrays; init fns take a PRNG key and return
  the dict; apply fns take (params, inputs, ...) and are jit/vmap/scan safe.
* activations compute in ``x.dtype`` (bf16 under the dry-run policy); params
  are stored in ``param_dtype``.
* attention comes in three execution strategies:
  - ``dense_attention``   — materializes scores; short sequences.
  - ``flash_attention``   — q-chunk x kv-chunk online-softmax scan; memory
    O(chunk^2) instead of O(S^2) (the jnp reference for the TPU kernel).
  - ``banded_attention``  — local-window variant that only *visits* the
    in-window band, giving truly sub-quadratic FLOPs (recurrentgemma).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

Params = dict

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, *, scale: float | None = None,
               bias: bool = False, dtype=jnp.float32) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: Params, x: jnp.ndarray, *, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * p["g"].astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10_000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10_000.0) -> jnp.ndarray:
    """x: (..., S, H, D) with D even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention strategies
# ---------------------------------------------------------------------------

_NEG = -1e30


def _expand_gqa(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B, S, Hq, D) -> (B, S, Hkv, G, D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def dense_attention(
    q: jnp.ndarray,            # (B, Sq, Hq, D)
    k: jnp.ndarray,            # (B, Sk, Hkv, D)
    v: jnp.ndarray,            # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: jnp.ndarray | int = 0,
    kv_len: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Reference attention; scores materialized.  GQA via head grouping.

    ``q_offset`` is the absolute position of q[0] (decode: cache length);
    ``kv_len`` masks padded cache entries beyond the valid length.
    """
    n_kv = k.shape[2]
    qg = _expand_gqa(q, n_kv)                              # B Sq Hkv G D
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq) + q_offset                       # (Sq,)
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= qpos[:, None] - kpos[None, :] < window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    logits = jnp.where(mask[None, None, None], logits.astype(jnp.float32), _NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    b, _, hkv, g, dv = out.shape
    return out.reshape(b, sq, hkv * g, dv)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    """Online-softmax blockwise attention (jnp reference of the TPU pattern).

    Peak live memory is O(q_chunk x kv_chunk) scores instead of O(Sq x Sk).
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, dv = v.shape
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    assert sq % q_chunk == 0 and sk % kv_chunk == 0, "chunk must divide length"
    nq, nk = sq // q_chunk, sk // kv_chunk
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)

    qs = q.reshape(b, nq, q_chunk, hkv, g, d)
    ks = k.reshape(b, nk, kv_chunk, hkv, d)
    vs = v.reshape(b, nk, kv_chunk, hkv, dv)

    def q_block(carry, qi):
        qb = qs[:, qi]  # (B, qc, Hkv, G, D)
        qpos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_block(state, ki):
            m, l, acc = state
            kb = ks[:, ki]
            vb = vs[:, ki]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
            if causal:
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(qb.dtype), vb
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk), _NEG, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dv), jnp.float32)
        # rematerialized: the backward pass recomputes each block's scores
        # instead of keeping all (Sq x Sk) of them alive
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_block), (m0, l0, a0), jnp.arange(nk)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, hq, dv)
        return carry, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_block, (), jnp.arange(nq))
    # blocks: (nq, B, q_chunk, Hq, Dv)
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dv)


def banded_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    window: int,
    q_chunk: int = 1024,
) -> jnp.ndarray:
    """Causal local attention visiting only the in-window band.

    For each q-chunk, a static-size slice of (window + q_chunk) keys is
    gathered with dynamic_slice — FLOPs O(S * window), not O(S^2).
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, dv = v.shape
    q_chunk = min(q_chunk, sq)
    assert sq % q_chunk == 0
    assert sq == sk, "banded attention is self-attention"
    nq = sq // q_chunk
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    band = window + q_chunk  # static slice width

    # left-pad keys so every slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (band - q_chunk, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (band - q_chunk, 0), (0, 0), (0, 0)))
    qs = q.reshape(b, nq, q_chunk, hkv, g, d)

    def q_block(carry, qi):
        qb = qs[:, qi]
        start = qi * q_chunk  # slice [start, start+band) of padded == kv pos start-window..start+qc
        kb = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
        qpos = start + jnp.arange(q_chunk)                       # absolute
        kpos = start - window + jnp.arange(band)                 # absolute (may be <0 = pad)
        mask = (
            (qpos[:, None] >= kpos[None, :])
            & (qpos[:, None] - kpos[None, :] < window)
            & (kpos[None, :] >= 0)
        )
        s = jnp.where(mask[None, None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(qb.dtype), vb)
        return carry, out.reshape(b, q_chunk, hq, dv)

    _, blocks = jax.lax.scan(q_block, (), jnp.arange(nq))
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dv)


def pad_heads_for_tp(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, dm: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Padded-TP head layout for head counts not dividing the TP axis.

    Without this, XLA factors the TP axis into heads x head_dim and emits an
    all-reduce per attention chunk-pair (measured 3.7 TB/device/step on
    deepseek-coder-33b prefill, EXPERIMENTS.md §Perf iteration 4).

    Exact construction: kv heads are *repeated* ``rep = lcm(KV, dm)/KV``
    times; each real group's q heads are zero-padded from ``gq = H/KV`` to
    ``gq_pad = rep * ceil(gq/rep)``.  Group-major head order is preserved, so
    padded q slot ``r*gq_pad + o`` attends padded kv head
    ``r*rep + o // (gq_pad/rep)`` — a replica of real kv head ``r``: the math
    for every real head is unchanged.  Padded q rows produce garbage
    attention that the caller slices away, costing ``H_pad/H`` extra
    attention FLOPs for clean ``H_pad % dm == 0`` TP.

    Returns (q_pad, k_rep, v_rep, gq_pad); callers unpad the output with
    ``out.reshape(B, S, KV, gq_pad, D)[:, :, :, :gq]``.
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    rep = math.lcm(kv, dm) // kv
    gq_pad = rep * (-(-g // rep))
    qg = q.reshape(b, s, kv, g, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, gq_pad - g), (0, 0)))
    q_pad = qg.reshape(b, s, kv * gq_pad, d)
    k_rep = jnp.repeat(k, rep, axis=2)
    v_rep = jnp.repeat(v, rep, axis=2)
    return q_pad, k_rep, v_rep, gq_pad


def attention_any(
    q, k, v, *, causal=True, window=0, q_offset=0, kv_len=None,
    flash_threshold: int = 2048,
):
    """Dispatch to the right attention strategy for the shapes at hand."""
    sq, sk = q.shape[1], k.shape[1]
    if sq == 1 or sq * sk <= flash_threshold * flash_threshold // 4 or kv_len is not None:
        return dense_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset, kv_len=kv_len
        )
    if window > 0 and sq == sk:
        qc = _largest_chunk(sq, min(1024, window))
        return banded_attention(q, k, v, window=window, q_chunk=qc)
    return flash_attention(
        q, k, v, causal=causal,
        q_chunk=_largest_chunk(sq, 1024), kv_chunk=_largest_chunk(sk, 1024),
    )


def _largest_chunk(n: int, target: int) -> int:
    c = min(target, n)
    while n % c:
        c -= 1
    return c


# ---------------------------------------------------------------------------
# GQA attention block (params + apply), with KV cache support
# ---------------------------------------------------------------------------



def _attend_tp(q, k, v, n_heads, head_dim, *, causal, window=0):
    """attention_any with the padded-TP layout when the head count does not
    divide the model axis (see pad_heads_for_tp)."""
    from ..dist import context as dist_context

    ctx = dist_context.current()
    dm = ctx.model_size if ctx is not None else 1
    if dm > 1 and n_heads % dm == 0:
        pass  # clean TP; constrain_heads already pinned it in gqa_apply
    elif dm > 1:
        b, sq = q.shape[0], q.shape[1]
        n_kv = k.shape[2]
        g = n_heads // n_kv
        qp, kp, vp, gq_pad = pad_heads_for_tp(q, k, v, dm)
        qp = ctx.constrain_heads(qp)
        kp = ctx.constrain_heads(kp)
        vp = ctx.constrain_heads(vp)
        outp = attention_any(qp, kp, vp, causal=causal, window=window)
        out = outp.reshape(b, sq, n_kv, gq_pad, head_dim)[:, :, :, :g]
        return out.reshape(b, sq, n_heads, head_dim)
    return attention_any(q, k, v, causal=causal, window=window)


def gqa_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
             *, bias: bool = False, dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d_model, n_heads * head_dim, bias=bias, dtype=dtype),
        "wk": dense_init(kk, d_model, n_kv * head_dim, bias=bias, dtype=dtype),
        "wv": dense_init(kv, d_model, n_kv * head_dim, bias=bias, dtype=dtype),
        "wo": dense_init(ko, n_heads * head_dim, d_model,
                         scale=0.02 / math.sqrt(2), dtype=dtype),
    }


def gqa_apply(
    p: Params,
    x: jnp.ndarray,                      # (B, S, d)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    causal: bool = True,
    window: int = 0,
    rope_theta: float = 10_000.0,
    cache: Params | None = None,         # {"k","v","len"} for decode
    kv_source: jnp.ndarray | None = None,  # cross-attention context
) -> tuple[jnp.ndarray, Params | None]:
    from ..dist import context as dist_context

    b, s, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(b, s, n_heads, head_dim)
    src = x if kv_source is None else kv_source
    k = dense_apply(p["wk"], src).reshape(b, src.shape[1], n_kv, head_dim)
    v = dense_apply(p["wv"], src).reshape(b, src.shape[1], n_kv, head_dim)
    ctx = dist_context.current()
    if ctx is not None:
        # explicit head shardings: never let the partitioner split head_dim
        # (for head counts not dividing the TP axis it otherwise factors the
        # contraction dim and emits an all-reduce per attention chunk pair)
        q = ctx.constrain_heads(q)
        k = ctx.constrain_heads(k)
        v = ctx.constrain_heads(v)

    new_cache = None
    if kv_source is not None:
        # cross-attention: no positional rotation of image/context tokens
        out = _attend_tp(q, k, v, n_heads, head_dim, causal=False)
    elif cache is not None:
        offset = cache["len"]
        q = apply_rope(q, offset + jnp.arange(s), rope_theta)
        k = apply_rope(k, offset + jnp.arange(s), rope_theta)
        ck, cv, clen = cache["k"], cache["v"], cache["len"]
        k = k.astype(ck.dtype)
        v = v.astype(cv.dtype)
        max_len = ck.shape[1]
        if window > 0 and max_len == window:
            # ring buffer for local attention: O(window) cache.  Decode
            # (s == 1) uses dynamic_update_slice (partitioner-friendly);
            # multi-token writes fall back to a scatter.
            if s == 1:
                pos = clen % window
                ck = jax.lax.dynamic_update_slice_in_dim(ck, k, pos, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, v, pos, 1)
            else:
                idx = (clen + jnp.arange(s)) % window
                ck = ck.at[:, idx].set(k)
                cv = cv.at[:, idx].set(v)
            # unroll ring chronologically with the valid entries front-aligned
            valid = jnp.minimum(clen + s, window)
            order = (clen + s - valid + jnp.arange(window)) % window
            k_all = jnp.take(ck, order, axis=1)
            v_all = jnp.take(cv, order, axis=1)
            out = dense_attention(
                q, k_all, v_all, causal=True, q_offset=valid - s,
                kv_len=valid,
            )
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, clen, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, clen, 1)
            k_all, v_all = ck, cv
            out = dense_attention(
                q, k_all, v_all, causal=causal, window=window,
                q_offset=clen, kv_len=clen + s,
            )
        new_cache = {"k": ck, "v": cv, "len": clen + s}
    else:
        pos = jnp.arange(s)
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
        out = _attend_tp(q, k, v, n_heads, head_dim, causal=causal,
                         window=window)

    # attention over a higher-precision cache must not promote the residual
    out = out.astype(x.dtype)
    y = dense_apply(p["wo"], out.reshape(b, s, n_heads * head_dim))
    return y, new_cache


def gqa_init_cache(b: int, max_len: int, n_kv: int, head_dim: int, *,
                   window: int = 0, dtype=jnp.bfloat16) -> Params:
    length = window if window > 0 else max_len
    return {
        "k": jnp.zeros((b, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((b, length, n_kv, head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d_model, d_ff, dtype=dtype),
        "wg": dense_init(k2, d_model, d_ff, dtype=dtype),
        "wo": dense_init(k3, d_ff, d_model, scale=0.02 / math.sqrt(2), dtype=dtype),
    }


def swiglu_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return dense_apply(
        p["wo"], jax.nn.silu(dense_apply(p["wg"], x)) * dense_apply(p["wi"], x)
    )


def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"table": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embed_apply(p: Params, tokens: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return p["table"].astype(dtype)[tokens]


def unembed_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["table"].astype(x.dtype).T
