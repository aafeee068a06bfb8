"""The check decides ``correct`` by what the timed path produced.

A small granite-shaped cell runs the whole of a benchmark run on the CPU
(set-up steps and window through ``Trainer.run``, then the reference),
skipping only the look for a chip.  A sound run comes out correct; a run
with the timed path broken underneath comes out not correct, once for each
fault a one-chip training cell can have; and so does the control, the
reference computed in float8 in the program's place."""

import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.kinds import train

SEED = 2**31 + 11
# limits for this size, from its own readings on this seed: the program
# reads gaps of 1.4e-4 (loss), 0.0048 (first gradient) and 0.0035 (change);
# the control 4.4e-4, 0.063 and 0.0069; half of the batch 0.0068, 0.30, 0.020
LIMITS = {"loss_gap": {"limit": 5e-4}, "grad_gap": {"limit": 0.03},
          "update_gap": {"limit": 0.03}, "data_mismatch": {"limit": 0},
          "window_nonfinite": {"limit": 0}}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_cell():
    cell = harness.resolve("granite-L4.train-4k")
    cfg = dict(cell.config, hidden_size=64, intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, num_local_experts=8,
               num_experts_per_tok=2, vocab_size=512, attention_multiplier=0.25)
    cfg["program"] = dict(cfg["program"], global_batch=4)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, seq_len=32)
    cell.limits = LIMITS
    return cell


def run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(), CPU,
                            log=lambda *a, **k: None)


def test_sound_run_is_correct():
    out = run(small_cell())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def state_unchanged(monkeypatch, cell):
    from repro.train import train_step

    monkeypatch.setattr(train_step, "adamw_update", lambda params, grads, state, cfg: (
        params, state, {"grad_norm": jnp.zeros(()), "lr": jnp.zeros(())}))


def half_batch(monkeypatch, cell):
    from repro.train import train_step

    loss_fn = train_step.loss_fn

    def half(cfg, params, batch, *a, **k):
        return loss_fn(cfg, params, {n: x[: x.shape[0] // 2] for n, x in batch.items()},
                       *a, **k)

    monkeypatch.setattr(train_step, "loss_fn", half)


def token_altered(monkeypatch, cell):
    from repro.data.pipeline import SyntheticLM

    batch = SyntheticLM.batch
    vocab = cell.config["vocab_size"]

    def altered(self, step):
        out = dict(batch(self, step))
        out["tokens"] = out["tokens"].copy()
        out["tokens"][0, 5] = (out["tokens"][0, 5] + 1) % vocab
        return out

    monkeypatch.setattr(SyntheticLM, "batch", altered)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, token_altered],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    cell = small_cell()
    fault(monkeypatch, cell)
    out = run(cell)
    assert not out["correct"], out["checks"]


def test_control_in_float8_is_not_correct():
    cell = small_cell()
    batches = train.regenerate(cell, SEED, [0, 1, 2])
    ref = train.reference(cell).train(SEED, batches)
    control = train.reference(cell, mode="fp8").train(SEED, batches)
    found = train.numbers(control, ref)
    ok, checks = harness.judge(dict(found, data_mismatch=0, window_nonfinite=0), LIMITS)
    assert not ok, checks


def test_step_hlo_is_the_compiled_step():
    from bench import hlo_groups

    cell = small_cell()
    text = train.step_hlo(cell)
    assert "ENTRY" in text
    assert hlo_groups.collectives_over(text, {"pod": 1, "data": 1, "model": 1}, "pod") == {}
