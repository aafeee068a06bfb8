"""AdamW with cosine schedule, grad clipping and a dtype policy.

State dtype is configurable so very large models (deepseek-v3-671b) can run
a lean bf16 m/v policy that actually fits the per-device HBM budget — the
policy used is reported by the dry-run memory analysis.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "adamw_init", "adamw_update", "cosine_lr", "global_norm"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    state_dtype: Any = jnp.float32     # m/v dtype (bf16 for lean policy)


def adamw_init(params: Any, cfg: AdamWConfig) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, cfg.state_dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def cosine_lr(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    return cfg.lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))


def global_norm(tree: Any) -> jnp.ndarray:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


def adamw_update(
    params: Any, grads: Any, state: dict, cfg: AdamWConfig
) -> tuple[Any, dict, dict]:
    """One optimizer step.  Returns (new_params, new_state, metrics)."""
    with jax.named_scope("adam"):
        return _adamw_update(params, grads, state, cfg)


def _adamw_update(params, grads, state, cfg):
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    lr = cosine_lr(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32 = m.astype(jnp.float32) * cfg.b1 + (1 - cfg.b1) * g
        v32 = v.astype(jnp.float32) * cfg.b2 + (1 - cfg.b2) * jnp.square(g)
        mhat = m32 / b1c
        vhat = v32 / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        newp = p.astype(jnp.float32) - lr * delta
        return newp.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)

    flat_p, td = jax.tree.flatten(params)
    flat_g = td.flatten_up_to(grads)
    flat_m = td.flatten_up_to(state["m"])
    flat_v = td.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = td.unflatten([o[0] for o in out])
    new_state = {
        "m": td.unflatten([o[1] for o in out]),
        "v": td.unflatten([o[2] for o in out]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
