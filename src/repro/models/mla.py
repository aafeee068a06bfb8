"""Multi-head Latent Attention (DeepSeek-V2/V3).

Queries and keys/values are produced from low-rank latent compressions:

    c_q  = x W_dq            (q_lora_rank)
    q    = RMSNorm(c_q) W_uq          -> per-head [nope | rope] parts
           (or q = x W_q where ``q_lora_rank`` is None)
    c_kv = x W_dkv           (kv_lora_rank)    <- THE KV cache (plus k_rope)
    k    = RMSNorm(c_kv) W_uk + shared k_rope
    v    = RMSNorm(c_kv) W_uv

Decode caches only (c_kv, k_rope): (S, kv_lora_rank + rope_dim) per token —
~10x smaller than GQA at these dims.  Attention itself is standard softmax
over qk_head_dim with a separate v_head_dim.  The latent projections and
their norms run in the named scope ``mla_latent``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig
from .layers import (
    Params,
    apply_rope,
    dense_apply,
    dense_init,
    dense_attention,
    flash_attention,
    rmsnorm_apply,
    rmsnorm_init,
    _largest_chunk,
)

__all__ = ["mla_init", "mla_apply", "mla_init_cache"]


def mla_init(key, d_model: int, n_heads: int, mla: MLAConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 7)
    qk, rope = mla.qk_nope_head_dim, mla.qk_rope_head_dim
    if mla.q_lora_rank:
        query = {
            "wdq": dense_init(ks[0], d_model, mla.q_lora_rank, dtype=dtype),
            "q_norm": rmsnorm_init(mla.q_lora_rank, dtype),
            "wuq": dense_init(ks[1], mla.q_lora_rank, n_heads * (qk + rope), dtype=dtype),
        }
    else:
        query = {"wq": dense_init(ks[0], d_model, n_heads * (qk + rope), dtype=dtype)}
    return {
        **query,
        "wdkv": dense_init(ks[2], d_model, mla.kv_lora_rank, dtype=dtype),
        "kv_norm": rmsnorm_init(mla.kv_lora_rank, dtype),
        "wuk": dense_init(ks[3], mla.kv_lora_rank, n_heads * qk, dtype=dtype),
        "wuv": dense_init(ks[4], mla.kv_lora_rank, n_heads * mla.v_head_dim, dtype=dtype),
        "wkr": dense_init(ks[5], d_model, rope, dtype=dtype),
        "wo": dense_init(
            ks[6], n_heads * mla.v_head_dim, d_model,
            scale=0.02 / math.sqrt(2), dtype=dtype,
        ),
    }


def mla_init_cache(b: int, max_len: int, mla: MLAConfig, dtype=jnp.bfloat16) -> Params:
    return {
        "ckv": jnp.zeros((b, max_len, mla.kv_lora_rank), dtype),
        "kr": jnp.zeros((b, max_len, mla.qk_rope_head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def _project_kv(p: Params, ckv: jnp.ndarray, n_heads: int, mla: MLAConfig, eps: float):
    with jax.named_scope("mla_latent"):
        ckv_n = rmsnorm_apply(p["kv_norm"], ckv, eps=eps)
        b, s, _ = ckv.shape
        k_nope = dense_apply(p["wuk"], ckv_n).reshape(b, s, n_heads, mla.qk_nope_head_dim)
        v = dense_apply(p["wuv"], ckv_n).reshape(b, s, n_heads, mla.v_head_dim)
    return k_nope, v


def mla_apply(
    p: Params,
    x: jnp.ndarray,
    *,
    n_heads: int,
    mla: MLAConfig,
    causal: bool = True,
    rope_theta: float = 10_000.0,
    eps: float = 1e-6,
    cache: Params | None = None,
) -> tuple[jnp.ndarray, Params | None]:
    """``eps`` is the latent norms' epsilon."""
    b, s, _ = x.shape
    qk, rope_d = mla.qk_nope_head_dim, mla.qk_rope_head_dim

    with jax.named_scope("mla_latent"):
        if "wq" not in p:
            cq = rmsnorm_apply(p["q_norm"], dense_apply(p["wdq"], x), eps=eps)
        ckv_new = dense_apply(p["wdkv"], x)                   # (B, S, r_kv)
        kr_new = dense_apply(p["wkr"], x)                     # (B, S, rope_d)
    q = dense_apply(p["wq"], x) if "wq" in p else dense_apply(p["wuq"], cq)
    q = q.reshape(b, s, n_heads, qk + rope_d)
    q_nope, q_rope = q[..., :qk], q[..., qk:]

    new_cache = None
    if cache is not None:
        clen = cache["len"]
        pos = clen + jnp.arange(s)
        q_rope = apply_rope(q_rope, pos, rope_theta)
        kr_new = apply_rope(kr_new[:, :, None, :], pos, rope_theta)[:, :, 0]
        ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new.astype(cache["ckv"].dtype), clen, 1)
        kr = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_new.astype(cache["kr"].dtype), clen, 1)
        new_cache = {"ckv": ckv, "kr": kr, "len": clen + s}
        k_nope, v = _project_kv(p, ckv.astype(x.dtype), n_heads, mla, eps)
        k_rope_b = jnp.broadcast_to(kr[:, :, None, :].astype(x.dtype),
                                    (b, kr.shape[1], n_heads, rope_d))
        k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = dense_attention(
            q_full, k, v, causal=causal, q_offset=clen, kv_len=clen + s
        )
    else:
        pos = jnp.arange(s)
        q_rope = apply_rope(q_rope, pos, rope_theta)
        kr_rot = apply_rope(kr_new[:, :, None, :], pos, rope_theta)[:, :, 0]
        k_nope, v = _project_kv(p, ckv_new, n_heads, mla, eps)
        k_rope_b = jnp.broadcast_to(kr_rot[:, :, None, :], (b, s, n_heads, rope_d))
        k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        if s > 2048:
            out = flash_attention(
                q_full, k, v, causal=causal,
                q_chunk=_largest_chunk(s, 1024), kv_chunk=_largest_chunk(s, 1024),
            )
        else:
            out = dense_attention(q_full, k, v, causal=causal)

    y = dense_apply(p["wo"], out.reshape(b, s, n_heads * mla.v_head_dim))
    return y, new_cache
