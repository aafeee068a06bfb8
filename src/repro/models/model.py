"""Composable model assembly for all assigned architectures.

A model is a stack of residual blocks described by ``cfg.block_list()``;
heterogeneous stacks are compiled compactly via the scan partition
(prefix unrolled | pattern super-blocks scanned | suffix unrolled), so a
100-layer VLM lowers to one scan body instead of 100 inlined layers.

Public API:
    init_params(cfg, key)                      -> params pytree
    forward(cfg, params, batch, cache=None)    -> (logits, new_cache)
    init_cache(cfg, batch, max_len)            -> decode cache pytree
    param_count(cfg)                           -> int (via eval_shape)
"""

from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import Block, ModelConfig
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import rwkv6 as rwkv_mod
from .layers import (
    Params,
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    gqa_apply,
    gqa_init,
    gqa_init_cache,
    rmsnorm_apply,
    rmsnorm_init,
    unembed_apply,
)

__all__ = ["init_params", "forward", "init_cache", "param_count", "num_params"]

# mixers whose work the "attn" named scope covers (the profile's attention)
_ATTN_MIXERS = ("attn", "attn_local", "attn_cross", "mla")


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig, block: Block) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p: Params = {"norm1": rmsnorm_init(d)}
    hd = cfg.resolved_head_dim
    if block.mixer in ("attn", "attn_local", "attn_cross"):
        p["mixer"] = gqa_init(k1, d, cfg.n_heads, cfg.n_kv_heads, hd,
                              bias=cfg.qkv_bias)
    elif block.mixer == "mla":
        assert cfg.mla is not None
        p["mixer"] = mla_mod.mla_init(k1, d, cfg.n_heads, cfg.mla)
    elif block.mixer == "rwkv":
        p["mixer"] = rwkv_mod.rwkv_tmix_init(k1, d, cfg.rwkv_head_dim)
    elif block.mixer == "rglru":
        p["mixer"] = rglru_mod.rglru_block_init(
            k1, d, cfg.rglru_lru_width or d, cfg.rglru_conv_width
        )
    else:
        raise ValueError(f"unknown mixer {block.mixer!r}")

    if block.ffn != "none":
        p["norm2"] = rmsnorm_init(d)
    if block.ffn == "dense":
        from .layers import swiglu_init

        p["ffn"] = swiglu_init(k2, d, cfg.d_ff)
    elif block.ffn == "moe":
        assert cfg.moe is not None
        p["ffn"] = moe_mod.moe_init(
            k2, d, cfg.moe.n_experts, cfg.moe.d_expert,
            n_shared=cfg.moe.n_shared, d_shared=cfg.moe.d_shared,
        )
    elif block.ffn == "rwkv_cmix":
        p["ffn"] = rwkv_mod.rwkv_cmix_init(k2, d, cfg.d_ff)
    elif block.ffn != "none":
        raise ValueError(f"unknown ffn {block.ffn!r}")
    return p


def _block_cache(cfg: ModelConfig, block: Block, b: int, max_len: int,
                 dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    if block.mixer == "attn":
        return gqa_init_cache(b, max_len, cfg.n_kv_heads, hd, dtype=dtype)
    if block.mixer == "attn_local":
        return gqa_init_cache(
            b, max_len, cfg.n_kv_heads, hd,
            window=min(cfg.local_window, max_len), dtype=dtype,
        )
    if block.mixer == "attn_cross":
        return {"len": jnp.zeros((), jnp.int32)}  # context static; nothing cached
    if block.mixer == "mla":
        return mla_mod.mla_init_cache(b, max_len, cfg.mla, dtype)
    if block.mixer == "rwkv":
        return rwkv_mod.rwkv_init_state(b, cfg.d_model, cfg.rwkv_head_dim,
                                        dtype=dtype)
    if block.mixer == "rglru":
        return rglru_mod.rglru_init_state(
            b, cfg.rglru_lru_width or cfg.d_model, cfg.rglru_conv_width,
            dtype=dtype,
        )
    raise ValueError(block.mixer)


def _block_apply(
    cfg: ModelConfig,
    block: Block,
    p: Params,
    x: jnp.ndarray,
    *,
    img_ctx: jnp.ndarray | None = None,
    cache: Params | None = None,
):
    eps = cfg.norm_eps
    h = rmsnorm_apply(p["norm1"], x, eps=eps)
    new_cache = cache
    hd = cfg.resolved_head_dim

    scope = (jax.named_scope("attn") if block.mixer in _ATTN_MIXERS
             else contextlib.nullcontext())
    with scope:
        if block.mixer in ("attn", "attn_local"):
            window = cfg.local_window if block.mixer == "attn_local" else 0
            y, new_attn_cache = gqa_apply(
                p["mixer"], h,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                causal=cfg.causal, window=window, rope_theta=cfg.rope_theta,
                cache=cache,
            )
            if cache is not None:
                new_cache = new_attn_cache
        elif block.mixer == "attn_cross":
            assert img_ctx is not None, "cross-attention block needs image context"
            y, _ = gqa_apply(
                p["mixer"], h,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                causal=False, rope_theta=cfg.rope_theta, kv_source=img_ctx,
            )
            if cache is not None:
                new_cache = {"len": cache["len"] + x.shape[1]}
        elif block.mixer == "mla":
            y, new_mla_cache = mla_mod.mla_apply(
                p["mixer"], h, n_heads=cfg.n_heads, mla=cfg.mla,
                causal=cfg.causal, rope_theta=cfg.rope_theta, cache=cache,
            )
            if cache is not None:
                new_cache = new_mla_cache
        elif block.mixer == "rwkv":
            y, new_t = rwkv_mod.rwkv_tmix_apply(
                p["mixer"], h, head_dim=cfg.rwkv_head_dim,
                state=cache["tmix"] if cache is not None else None,
            )
            if cache is not None:
                new_cache = dict(cache)
                new_cache["tmix"] = new_t
        elif block.mixer == "rglru":
            y, new_r = rglru_mod.rglru_block_apply(
                p["mixer"], h, state=cache if cache is not None else None
            )
            if cache is not None:
                new_cache = new_r
        else:
            raise ValueError(block.mixer)
    x = x + y

    if block.ffn == "none":
        return x, new_cache
    h2 = rmsnorm_apply(p["norm2"], x, eps=eps)
    if block.ffn == "dense":
        from .layers import swiglu_apply

        x = x + swiglu_apply(p["ffn"], h2)
    elif block.ffn == "moe":
        x = x + moe_mod.moe_apply(
            p["ffn"], h2, top_k=cfg.moe.top_k,
            capacity_factor=cfg.moe.capacity_factor,
        )
    elif block.ffn == "rwkv_cmix":
        y2, new_c = rwkv_mod.rwkv_cmix_apply(
            p["ffn"], h2,
            state=cache["cmix"] if (cache is not None and block.mixer == "rwkv") else None,
        )
        x = x + y2
        if cache is not None and block.mixer == "rwkv":
            new_cache = dict(new_cache)
            new_cache["cmix"] = new_c
    return x, new_cache


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Params:
    prefix, n_scan, pattern, suffix = cfg.scan_partition()
    k_embed, k_head, k_pre, k_scan, k_suf = jax.random.split(key, 5)

    params: Params = {}
    if cfg.frontend == "token":
        params["embed"] = embed_init(k_embed, cfg.vocab_size, cfg.d_model)
    else:
        # modality frontend is a stub: inputs arrive as embeddings; a single
        # projection adapts them (stands in for the conv/patch stack)
        params["embed_proj"] = dense_init(k_embed, cfg.d_model, cfg.d_model)

    params["prefix"] = tuple(
        _block_init(k, cfg, b)
        for k, b in zip(jax.random.split(k_pre, max(len(prefix), 1)), prefix)
    )
    if n_scan > 0:
        def init_superblock(k):
            kk = jax.random.split(k, len(pattern))
            return tuple(_block_init(ki, cfg, b) for ki, b in zip(kk, pattern))

        params["scan"] = jax.vmap(init_superblock)(
            jax.random.split(k_scan, n_scan)
        )
    params["suffix"] = tuple(
        _block_init(k, cfg, b)
        for k, b in zip(jax.random.split(k_suf, max(len(suffix), 1)), suffix)
    )
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings or cfg.frontend != "token":
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                       scale=0.02)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    prefix, n_scan, pattern, suffix = cfg.scan_partition()
    cache: Params = {
        "prefix": tuple(_block_cache(cfg, b, batch, max_len, dtype) for b in prefix),
        "suffix": tuple(_block_cache(cfg, b, batch, max_len, dtype) for b in suffix),
    }
    if n_scan > 0:
        one = tuple(_block_cache(cfg, b, batch, max_len, dtype) for b in pattern)
        cache["scan"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_scan,) + a.shape).copy(), one
        )
    return cache


def forward(
    cfg: ModelConfig,
    params: Params,
    batch: dict[str, jnp.ndarray],
    *,
    cache: Params | None = None,
    compute_dtype=jnp.bfloat16,
    act_constrain=None,
    embed_fn=None,
):
    """Run the model.  ``batch`` has "tokens" (B,S) or "embeds" (B,S,d),
    optionally "img" (B,N_img,d) for VLM cross-attention.  Returns
    (logits, new_cache).

    ``act_constrain`` (optional) is applied to the residual-stream activation
    at every block boundary — the hook the distributed trainer uses to pin
    activation shardings so GSPMD never resolves a weight/activation conflict
    by replicating the batch.  ``embed_fn(embed_params, tokens, dtype)``
    optionally overrides the vocab lookup (the trainer supplies an explicitly
    sharded implementation; XLA's gather partitioner is not trusted with it).
    """
    prefix, n_scan, pattern, suffix = cfg.scan_partition()
    ac = act_constrain if act_constrain is not None else (lambda x: x)

    if cfg.frontend == "token":
        with jax.named_scope("embed"):
            if embed_fn is not None:
                x = embed_fn(params["embed"], batch["tokens"], compute_dtype)
            else:
                x = embed_apply(params["embed"], batch["tokens"], dtype=compute_dtype)
    else:
        x = dense_apply(params["embed_proj"], batch["embeds"].astype(compute_dtype))
    img_ctx = batch.get("img")
    if img_ctx is not None:
        img_ctx = img_ctx.astype(compute_dtype)

    new_cache: Params = {"prefix": [], "suffix": []} if cache is not None else None

    def run_block(blk, p, xx, c):
        xx, nc = _block_apply(cfg, blk, p, ac(xx), img_ctx=img_ctx, cache=c)
        return ac(xx), nc

    for i, blk in enumerate(prefix):
        c = cache["prefix"][i] if cache is not None else None
        x, nc = run_block(blk, params["prefix"][i], x, c)
        if cache is not None:
            new_cache["prefix"].append(nc)

    if n_scan > 0:
        def superblock(xx, args):
            p_stack, c_stack = args
            ncs = []
            for j, blk in enumerate(pattern):
                c = c_stack[j] if c_stack is not None else None
                xx, nc = run_block(blk, p_stack[j], xx, c)
                ncs.append(nc)
            return xx, (tuple(ncs) if c_stack is not None else None)

        body = jax.checkpoint(superblock) if cfg.remat else superblock
        c_scan = cache["scan"] if cache is not None else None
        x, scan_caches = jax.lax.scan(
            body, x, (params["scan"], c_scan)
        )
        if cache is not None:
            new_cache["scan"] = scan_caches

    for i, blk in enumerate(suffix):
        c = cache["suffix"][i] if cache is not None else None
        x, nc = run_block(blk, params["suffix"][i], x, c)
        if cache is not None:
            new_cache["suffix"].append(nc)

    with jax.named_scope("logits"):
        x = rmsnorm_apply(params["final_norm"], ac(x), eps=cfg.norm_eps)
        if "lm_head" in params:
            logits = dense_apply(params["lm_head"], x)
        else:
            logits = unembed_apply(params["embed"], x)
    if cache is not None:
        new_cache["prefix"] = tuple(new_cache["prefix"])
        new_cache["suffix"] = tuple(new_cache["suffix"])
    return logits, new_cache


def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return sum(int(math.prod(l.shape)) for l in jax.tree.leaves(shapes))


num_params = param_count


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k + shared experts only)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    moe_blocks = sum(1 for b in cfg.block_list() if b.ffn == "moe")
    per_expert = 3 * cfg.d_model * cfg.moe.d_expert
    inactive = moe_blocks * (cfg.moe.n_experts - cfg.moe.top_k) * per_expert
    return total - inactive
