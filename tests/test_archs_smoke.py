"""Per-architecture smoke tests: reduced configs, one forward + one train
step on CPU, asserting output shapes and absence of NaNs; decode parity for
autoregressive archs (prefill+decode == full forward)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, applicable_shapes
from repro.configs.registry import ARCHS, get_config, get_smoke_config
from repro.models.model import (
    active_param_count,
    forward,
    init_cache,
    init_params,
    param_count,
)

B, S = 2, 32


def _batch(cfg, b=B, s=S, seed=0):
    rng = np.random.default_rng(seed)
    batch = {}
    if cfg.frontend == "token":
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(b, s)), jnp.int32
        )
    else:
        batch["embeds"] = jnp.asarray(
            rng.normal(0, 1, size=(b, s, cfg.d_model)), jnp.float32
        )
    if cfg.n_img_tokens:
        batch["img"] = jnp.asarray(
            rng.normal(0, 1, size=(b, cfg.n_img_tokens, cfg.d_model)), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, _ = jax.jit(lambda p, b: forward(cfg, p, b))(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step_reduces_loss_direction(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg, seed=1)

    if cfg.frontend == "token":
        labels = jnp.roll(batch["tokens"], -1, axis=1)
    else:
        labels = jnp.zeros((B, S), jnp.int32)

    def loss_fn(p):
        logits, _ = forward(cfg, p, batch)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(lp, labels[..., None], axis=-1).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert bool(jnp.isfinite(loss))
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0
    # one SGD step lowers the loss on the same batch
    lr = 0.05
    new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    loss2 = jax.jit(loss_fn)(new_params)
    assert float(loss2) < float(loss) + 1e-6


@pytest.mark.parametrize(
    "arch",
    [a for a in ARCHS if not get_config(a).is_encoder_only],
)
def test_decode_matches_full_forward(arch):
    """Prefill + stepwise decode reproduces the full-sequence logits."""
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(2))
    batch = _batch(cfg, seed=2)
    full_logits, _ = forward(cfg, params, batch, compute_dtype=jnp.float32)

    cache = init_cache(cfg, B, max_len=S, dtype=jnp.float32)
    s_pre = S // 2
    toks = batch["tokens"]
    pre_batch = {k: (v[:, :s_pre] if k == "tokens" else v) for k, v in batch.items()}
    logits_pre, cache = forward(
        cfg, params, pre_batch, cache=cache, compute_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, -1]),
        np.asarray(full_logits[:, s_pre - 1]),
        rtol=2e-2, atol=2e-2,
    )
    # two decode steps
    for t in range(s_pre, s_pre + 2):
        step_batch = {"tokens": toks[:, t : t + 1]}
        if "img" in batch:
            step_batch["img"] = batch["img"]
        logits_t, cache = forward(
            cfg, params, step_batch, cache=cache, compute_dtype=jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(logits_t[:, 0]),
            np.asarray(full_logits[:, t]),
            rtol=2e-2, atol=2e-2,
        )


def test_applicable_shape_skips():
    """DESIGN.md §4: exactly 34 runnable cells with documented skips."""
    from repro.configs.registry import cells

    cs = cells()
    assert len(cs) == 34
    names = {(a, s.name) for a, s in cs}
    assert ("hubert-xlarge", "decode_32k") not in names
    assert ("hubert-xlarge", "long_500k") not in names
    assert ("minitron-8b", "long_500k") not in names
    assert ("deepseek-v3-671b", "long_500k") not in names
    assert ("moonlight-16b-a3b", "long_500k") not in names
    assert ("rwkv6-7b", "long_500k") in names
    assert ("recurrentgemma-9b", "long_500k") in names


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (spot checks for every arch)."""
    expect = {
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
        "deepseek-v3-671b": (61, 7168, 128, 128, 18432, 129280),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L and cfg.d_model == d
        assert cfg.n_heads == h and cfg.n_kv_heads == kv
        assert cfg.d_ff == ff and cfg.vocab_size == v
    # MoE extras
    dsv3 = get_config("deepseek-v3-671b")
    assert dsv3.moe.n_experts == 256 and dsv3.moe.top_k == 8
    assert dsv3.moe.n_shared == 1 and dsv3.moe.d_expert == 2048
    assert (dsv3.moe.scoring, dsv3.moe.routed_scale) == ("sigmoid", 2.5)
    moon = get_config("moonlight-16b-a3b")
    assert (moon.moe.n_experts, moon.moe.top_k, moon.moe.n_shared) == (64, 6, 2)
    assert (moon.moe.d_expert, moon.mla.q_lora_rank, moon.mla.kv_lora_rank) == (1408, None, 512)
    assert (moon.moe.scoring, moon.moe.routed_scale) == ("sigmoid", 2.446)
    gran = get_config("granite-moe-3b-a800m")
    assert gran.moe.n_experts == 40 and gran.moe.top_k == 8
    assert get_config("qwen2.5-32b").qkv_bias
    assert not get_config("hubert-xlarge").causal


def test_param_counts_plausible():
    assert abs(param_count(get_config("deepseek-v3-671b")) / 1e9 - 671) < 5
    assert abs(active_param_count(get_config("deepseek-v3-671b")) / 1e9 - 37) < 3
    assert abs(param_count(get_config("deepseek-7b")) / 1e9 - 7) < 1
    assert abs(param_count(get_config("deepseek-coder-33b")) / 1e9 - 33) < 2
    assert abs(param_count(get_config("moonlight-16b-a3b")) / 1e9 - 16) < 0.1
