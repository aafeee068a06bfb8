"""The DeepSeek-V3 block's routing in the program: sigmoid scores with a
selection bias, a layer that holds a share of the router's experts, the
bias rule outside the optimizer, and granite's softmax step unchanged to
the bit.  8 forced host devices."""

import dataclasses
import hashlib
import json
import os
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.dist import context as dist_context
from repro.dist.collectives import SyncConfig
from repro.launch.mesh import AXES, make_mesh
from repro.models import moe
from repro.optim.adamw import AdamWConfig
from repro.train import train_step as ts
from repro.train.trainer import Trainer, TrainerConfig

DIGESTS = Path(__file__).parent / "fixtures" / "granite_step_digests.json"
SIGMOID = MoEConfig(n_experts=16, top_k=4, d_expert=32, n_shared=2, d_shared=32,
                    capacity_factor=1.0, scoring="sigmoid", routed_scale=2.446,
                    balance_alpha=1e-4, bias_gamma=1e-3)


def mesh_of(shape):
    return make_mesh(shape, AXES, devices=jax.devices()[: int(np.prod(shape))])


def granite_step_digests(mesh_shape):
    """Losses and a digest of every parameter after two steps of a tiny
    granite (2 layers, 4 experts top-2) through ``Trainer``, bfloat16
    compute, geococo sync."""
    cfg = dataclasses.replace(
        get_config("granite-moe-3b-a800m"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=32, vocab_size=256, remat=True,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=32, capacity_factor=1.25))
    tcfg = ts.TrainConfig(sync=SyncConfig(strategy="geococo", density=0.25, chunk=64,
                                          min_leaf_size=64),
                          optim=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=8))
    data = DataConfig(vocab_size=256, seq_len=32, global_batch=4, seed=2**31 + 5)
    tr = Trainer(cfg, mesh_of(mesh_shape), tcfg,
                 TrainerConfig(steps=2, seed=2**31 + 5, log_every=100), data)
    tr.run()
    leaves = jax.tree_util.tree_flatten_with_path(jax.device_get(tr.params))[0]
    return {"losses": [h["loss"] for h in tr.history],
            "params": {jax.tree_util.keystr(k): hashlib.sha256(
                np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()[:16]
                for k, x in leaves}}


@pytest.mark.parametrize("path,mesh_shape", [("dense", (1, 1, 1)),
                                             ("expert_parallel", (1, 2, 2))])
def test_granite_step_is_unchanged_to_the_bit(path, mesh_shape):
    """Granite's softmax routing through both MoE paths gives the losses and
    parameters, bit for bit, that it gave before the router was shared and
    learnt sigmoid scores, a bias and a held share (stored digests)."""
    assert granite_step_digests(mesh_shape) == json.loads(DIGESTS.read_text())[path]


def layer_params(moe_cfg, d=64, seed=0):
    p = moe.moe_init(jax.random.PRNGKey(seed), d, moe_cfg)
    # a bias that moves the selection, as after some steps of the rule
    p["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1), p["bias"].shape)
    return p


def test_held_shares_sum_to_the_uncut_layer():
    """Four chips of an expert-parallel layer, each holding 4 of the 16
    experts and routing over all 16: their outputs, with the shared experts
    (which every chip computes alike) counted once, add up to the layer
    that holds all 16."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64), jnp.float32)
    whole = layer_params(SIGMOID)
    out, aux = moe._moe_apply_dense_dispatch(whole, x, SIGMOID)
    held = dataclasses.replace(SIGMOID, n_held=4)
    parts, kept = [], 0.0
    for s in range(4):
        p = dict(whole, **{w: whole[w][4 * s: 4 * s + 4] for w in ("wi", "wg", "wo")})
        y, a = moe._moe_apply_dense_dispatch(p, x, held, lo=4 * s)
        parts.append(y)
        kept += float(a["kept"])
        np.testing.assert_array_equal(a["load"], aux["load"])
    shared = moe._shared_experts(whole["shared"], x.reshape(-1, 64)).reshape(x.shape)
    np.testing.assert_allclose(sum(parts) - 3 * shared, out, rtol=1e-5, atol=1e-6)
    assert kept == float(aux["kept"]) < float(aux["held"])  # capacity 1.0 drops some


def test_routing_picks_by_biased_scores_and_gates_by_unbiased_ones():
    xf = jax.random.normal(jax.random.PRNGKey(3), (64, 64), jnp.float32)
    p = layer_params(SIGMOID)
    gate, expert, _, _, _, aux = moe.route(p, xf, SIGMOID, seqs=2)
    scores = jax.nn.sigmoid(xf @ p["router"]["w"])
    want = jax.lax.top_k(scores + p["bias"], SIGMOID.top_k)[1]
    np.testing.assert_array_equal(expert, want)
    g = jnp.take_along_axis(scores, want, axis=-1)
    np.testing.assert_allclose(gate, 2.446 * g / g.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_array_equal(aux["load"], jax.nn.one_hot(want, 16).sum((0, 1)))
    # the balance term: alpha * mean over sequences of sum_i f_i P_i
    counts = jax.nn.one_hot(want, 16).reshape(2, 32 * 4, 16).sum(1)
    share = (scores / scores.sum(-1, keepdims=True)).reshape(2, 32, 16).mean(1)
    bal = 1e-4 * (counts * 16 / (4 * 32) * share).sum(-1).mean()
    np.testing.assert_allclose(aux["balance"], bal, rtol=1e-6)


def test_bias_moves_one_step_towards_the_mean_load():
    load = jnp.array([[3.0, 1.0, 2.0, 2.0], [0.0, 4.0, 4.0, 0.0]])
    out = moe.bias_step(jnp.zeros((2, 4)), load, 0.5)
    np.testing.assert_array_equal(out, [[-0.5, 0.5, 0.0, 0.0], [0.5, -0.5, -0.5, 0.5]])


def test_expert_parallel_path_routes_as_the_one_device_path():
    """The manual expert-parallel path (experts over `model`) shares the
    router: sigmoid scores, a held share and its capacity give the one
    device path's output."""
    held = dataclasses.replace(SIGMOID, n_held=6, balance_alpha=0.0, bias_gamma=0.0)
    p = moe.moe_init(jax.random.PRNGKey(0), 64, held)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64), jnp.float32)
    one, _ = moe._moe_apply_dense_dispatch(p, x, held)
    mesh = mesh_of((1, 1, 4))
    with dist_context.distribution(mesh):
        ep, aux = jax.jit(lambda p, x: moe.moe_apply(p, x, held))(p, x)
    assert aux is None
    np.testing.assert_allclose(ep, one, rtol=1e-5, atol=1e-6)


def smoke_trainer(arch, mesh_shape=(1, 1, 1), steps=2):
    cfg = get_smoke_config(arch)
    tcfg = ts.TrainConfig(sync=SyncConfig(strategy="hier"),
                          optim=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=8),
                          compute_dtype=jnp.float32)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2, seed=2**31 + 9)
    return Trainer(cfg, mesh_of(mesh_shape), tcfg,
                   TrainerConfig(steps=steps, seed=2**31 + 9, log_every=100), data)


def test_bias_moves_by_its_rule_and_not_by_adam():
    """The selection bias gets no gradient and neither Adam nor weight
    decay: after each step it is the loads' sign rule, its moments zero."""
    tr = smoke_trainer("moonlight-16b-a3b", steps=1)
    b0 = np.asarray(tr.params["scan"][0]["ffn"]["bias"])
    tr.run()
    b1 = np.asarray(tr.params["scan"][0]["ffn"]["bias"])
    assert not b0.any()
    assert set(np.unique(np.round(b1 / 1e-3))) <= {-1.0, 0.0, 1.0} and b1.any()
    assert not np.asarray(tr.opt_state["m"]["scan"][0]["ffn"]["bias"]).any()
    assert not np.asarray(tr.opt_state["v"]["scan"][0]["ffn"]["bias"]).any()


@pytest.mark.parametrize("arch,counted", [("moonlight-16b-a3b", True),
                                          ("granite-moe-3b-a800m", False)])
def test_routing_counters_in_the_history(arch, counted):
    tr = smoke_trainer(arch)
    hist = tr.run()
    for h in hist:
        assert ("moe_load_peak" in h) == ("moe_drop_share" in h) == counted
        if counted:
            assert h["moe_load_peak"] >= 1.0 and 0.0 <= h["moe_drop_share"] < 1.0


def test_pod_step_refuses_a_bias_rule():
    cfg = get_smoke_config("moonlight-16b-a3b")
    with pytest.raises(NotImplementedError, match="bias rule"):
        ts.build_train_step(cfg, mesh_of((2, 1, 1)),
                            ts.TrainConfig(sync=SyncConfig(strategy="hier")))
