"""The DeepSeek-V3-block cell (``moonlight-L5.train-8k``): its files
resolve, its kind maps the config's widths and counts its FLOPs by hand,
the program agrees with ``bench/references/deepseek_v3_moe.py`` on a tiny
Moonlight-shaped config, and the check fails a run with any part of the
block planted out, as it fails the float8 control."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.kinds import train as plain_train

WORKLOAD = "moonlight-L5.train-8k"
SEED = 2**31 + 11
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# limits for the tiny size, from its own readings on this seed: the program
# reads gaps of 2.2e-4 (loss), 0.012 (first gradient), 0.0048 (change) and
# 0.0044 (bias); the control 6.4e-4, 0.12, 0.016, 0.087; half of the batch
# 0.0083, 0.15, 0.26; without the shared experts 0.0029, 1, 1; without the
# balance term 1.9e-4, 0.012, 0.026; without the bias rule a bias gap of 1
LIMITS = {"loss_gap": {"limit": 5e-4}, "grad_gap": {"limit": 0.03},
          "update_gap": {"limit": 0.008}, "bias_gap": {"limit": 0.02},
          "data_mismatch": {"limit": 0}, "window_nonfinite": {"limit": 0}}


def kind():
    return harness.kind_module(harness.resolve(WORKLOAD))


def tiny_config(cfg: dict) -> dict:
    """Moonlight's shape at a tiny size: no query latent, one dense layer
    then two MoE layers holding 4 of a router's 16 experts, top-3, 2 shared
    experts, an untied head."""
    return dict(cfg, hidden_size=64, intermediate_size=96, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
                num_hidden_layers=3, n_routed_experts=4, router_width=16,
                num_experts_per_tok=3, vocab_size=512,
                program=dict(cfg["program"], global_batch=2))


def small_cell():
    cell = harness.resolve(WORKLOAD)
    cell.config = tiny_config(cell.config)
    cell.traffic = dict(cell.traffic, seq_len=64)
    cell.limits = LIMITS
    return cell


def test_the_cell_resolves_from_its_files():
    cell = harness.resolve(WORKLOAD)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_deepseek_v3"
    assert cell.config["program"]["arch"] == "moonlight-16b-a3b"
    assert callable(harness.reference_module(cell).Reference)
    assert {m["name"] for m in cell.per_layer} == {
        "data_ms", "device_idle_share", "device_busy_ms", "step_mfu",
        "mla_latent_ms", "moe_shared_ms"}
    assert set(cell.limits) == set(LIMITS)
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]).read)
    assert {c["name"] for c in [m for m in cell.end_to_end]} == {
        "train_tokens_per_s", "setup_s"}


def test_the_kinds_model_config_has_the_files_widths():
    from repro.models.model import param_count

    cfg = harness.resolve(WORKLOAD).config
    m = kind().model_config(cfg)
    assert (m.n_layers, m.d_model, m.n_heads, m.d_ff, m.vocab_size) == (
        5, 2048, 16, 11264, 20480)
    assert (m.norm_eps, m.rope_theta, m.tie_embeddings) == (1e-5, 50000.0, False)
    assert [b.ffn for b in m.block_list()] == ["dense", "moe", "moe", "moe", "moe"]
    assert {b.mixer for b in m.block_list()} == {"mla"}
    assert dataclasses.asdict(m.mla) == dict(q_lora_rank=None, kv_lora_rank=512,
                                             qk_nope_head_dim=128, qk_rope_head_dim=64,
                                             v_head_dim=128)
    e = m.moe
    assert (e.n_experts, e.held, e.top_k, e.d_expert, e.n_shared, e.shared_hidden) == (
        64, 8, 6, 1408, 2, 1408)
    assert (e.scoring, e.routed_scale, e.balance_alpha, e.bias_gamma) == (
        "sigmoid", 2.446, 1e-4, 1e-3)
    # the parameters on the chip: 83.0 M dense layer, 100.4 M a MoE layer,
    # 83.9 M embedding and head, and each MoE layer's 64 biases
    assert param_count(m) == pytest.approx(568.5e6, rel=1e-3)


def test_model_flops_match_the_hand_count():
    k = kind()
    cfg = harness.resolve(WORKLOAD).config
    mla = 2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256 + 16 * 128 * 2048
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + (2 + 6 * 8 / 64) * expert
    hand = 5 * mla + 3 * 2048 * 11264 + 4 * moe + 2048 * 20480
    assert k.active_matmul_params(cfg) == pytest.approx(hand)
    attention = 3 * 5 * 2 * 16 * (192 + 128) * (8192 + 1) / 2
    assert k.train_flops_per_token(cfg, 8192) == pytest.approx(6 * hand + attention)
    assert k.train_flops_per_token(cfg, 8192) / 1e9 == pytest.approx(2.28, abs=0.01)


def test_train_py_is_untouched_by_the_rebinding():
    from bench import flops

    k = kind()
    assert k.train is not plain_train
    assert k.train.model_config is k.model_config and k.train.run is k.run
    assert plain_train.model_config is not k.model_config
    assert plain_train.model_config.__module__ == plain_train.__name__
    assert plain_train.flops is flops and plain_train.numbers is not k.numbers
    granite = harness.resolve("granite-L4.train-4k").config
    assert plain_train.model_config(granite).moe.scoring == "softmax"


def program_and_reference(seed=SEED):
    """The program's loss and gradient (float32) and the reference's on one
    batch of the tiny cell, from the seed's parameters."""
    from repro.models.model import init_params
    from repro.train.train_step import loss_fn

    cell = small_cell()
    cfg = cell.config
    mcfg = kind().model_config(cfg)
    ref_mod = harness.reference_module(cell)
    batch = kind().train.regenerate(cell, seed, [0])[0]
    params = init_params(mcfg, jax.random.PRNGKey(seed))
    ref_params = ref_mod.init_params(cfg, jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(mcfg, p, {k: jnp.asarray(v) for k, v in batch.items()},
                              jnp.float32), has_aux=True)(params)
        ref = ref_mod.Reference(cfg, cell.traffic["optimizer"], 64, 2)
        offsets = jnp.zeros((2, 16), jnp.int32)
        ref_loss, ref_grads = 0.0, jax.tree.map(jnp.zeros_like, ref_params)
        for r in range(2):
            l, counts, ref_grads = ref._grad_add(
                ref_params, ref_grads, jnp.asarray(batch["tokens"][r]),
                jnp.asarray(batch["labels"][r]), offsets)
            offsets = offsets + counts
            ref_loss += float(l) / 2
    return params, ref_params, float(loss), ref_loss, grads, jax.tree.map(
        lambda g: g / 2, ref_grads)


def test_program_matches_the_reference_loss_and_gradients():
    params, ref_params, loss, ref_loss, grads, ref_grads = program_and_reference()
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_params)[0])
    assert set(map(jax.tree_util.keystr, flat)) == set(map(jax.tree_util.keystr, ref_flat))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(a, b)          # the trainer's init recipe
    # float32 on both sides, different summation orders: round-off only
    assert loss == pytest.approx(ref_loss, rel=1e-5)
    names = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(grads)[0]]
    for name, a, b in zip(names, jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        scale = float(np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * scale + 1e-12, err_msg=name)


def test_bias_rule_after_two_steps_equals_the_reference():
    from repro.data.pipeline import DataConfig
    from repro.dist.collectives import SyncConfig
    from repro.launch.mesh import AXES, make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cell = small_cell()
    opt = cell.traffic["optimizer"]
    tcfg = TrainConfig(sync=SyncConfig(strategy="hier"), optim=AdamWConfig(**opt),
                       compute_dtype=jnp.float32)
    data = DataConfig(vocab_size=512, seq_len=64, global_batch=2, seed=SEED,
                      **cell.traffic["data"])
    tr = Trainer(kind().model_config(cell.config), make_mesh((1, 1, 1), AXES), tcfg,
                 TrainerConfig(steps=2, seed=SEED, log_every=100), data)
    batches = [tr.data.batch(n) for n in range(2)]
    with jax.default_matmul_precision("highest"):
        tr.run()
    ref = harness.reference_module(cell).Reference(cell.config, opt, 64, 2).train(
        SEED, batches)
    (name,) = ref["biases"]
    ours = np.asarray(tr.params["scan"][0]["ffn"]["bias"])
    assert ours.any()
    np.testing.assert_array_equal(ours, ref["biases"][name])


def run(cell):
    return harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(), CPU,
                            log=lambda *a, **k: None)


def test_sound_run_is_correct():
    out = run(small_cell())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def half_batch(monkeypatch):
    from repro.train import train_step

    loss_fn = train_step.loss_fn
    monkeypatch.setattr(train_step, "loss_fn", lambda cfg, params, batch, *a, **k: loss_fn(
        cfg, params, {n: x[: x.shape[0] // 2] for n, x in batch.items()}, *a, **k))


def no_shared_experts(monkeypatch):
    from repro.models import moe

    monkeypatch.setattr(moe, "_shared_experts", lambda sh, xf: jnp.zeros_like(xf))


def no_bias_rule(monkeypatch):
    from repro.train import train_step

    monkeypatch.setattr(train_step, "with_bias_step", lambda cfg, params, aux: params)


def no_balance_term(monkeypatch):
    from repro.train import train_step

    loss_fn = train_step.loss_fn

    def without(*a, **k):
        loss, aux = loss_fn(*a, **k)
        return loss - sum(r["balance"].sum() for r in train_step.moe_records(aux)), aux

    monkeypatch.setattr(train_step, "loss_fn", without)


@pytest.mark.parametrize("fault", [half_batch, no_shared_experts, no_bias_rule,
                                   no_balance_term])
def test_a_planted_fault_fails_a_limit(monkeypatch, fault):
    fault(monkeypatch)
    out = run(small_cell())
    assert not out["correct"], out["checks"]


def test_the_float8_control_fails_a_limit():
    """The reference in float8 in the program's place (the program states
    bfloat16) fails a limit the program passes."""
    cell = small_cell()
    k = kind()
    trainer = k.train.build_trainer(cell, SEED)
    spans = k.train.instrument(trainer)
    side = k.train.program_side(k.train.set_up_steps(trainer), trainer.tcfg.optim.b1)
    batches = spans.batches[:3]
    ref = k.train.reference(cell).train(SEED, batches)
    exact = {"data_mismatch": 0, "window_nonfinite": 0}
    assert harness.judge({**k.numbers(side, ref), **exact}, LIMITS)[0]
    control = {**k.numbers(k.train.reference(cell, mode="fp8").train(SEED, batches), ref),
               **exact}
    assert not harness.judge(control, LIMITS)[0], control
