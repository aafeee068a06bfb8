"""The four-chip pod cell: its files resolve, its check sees each fault the
exchange between pods can have, and its two readers of the pod exchange
read the right times.

A small granite-shaped cell runs the whole of a benchmark run on
``pod=2 x data=2`` of 4 forced host devices (set-up steps and window
through ``Trainer.run``, then the reference), skipping only the look for a
chip."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time
import types

import jax
import jax.numpy as jnp
import pytest

from bench import harness, scopes, trace
from bench.kinds import train_pods

CELL = "granite-L8-pod2x2.geococo-4k"
SEED = 2**31 + 11
# limits for this size, from its own readings on this seed: the program
# reads gaps of 1.6e-4 (loss), 0.0052 (first gradient), 0.0030 (change) and
# 0.011 (residual); the faults below read at least 0.032, 0.035 or 0.34
LIMITS = {"loss_gap": {"limit": 1e-3}, "grad_gap": {"limit": 0.02},
          "update_gap": {"limit": 0.02}, "residual_gap": {"limit": 0.1},
          "data_mismatch": {"limit": 0}, "window_nonfinite": {"limit": 0}}
CPU = {"platform": "cpu", "kind": "cpu", "count": 4}
MESH = {"pod": 2, "data": 2, "model": 1}


def small_cell():
    cell = harness.resolve(CELL)
    cfg = dict(cell.config, hidden_size=64, intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, num_local_experts=8,
               num_experts_per_tok=2, vocab_size=512, attention_multiplier=0.25)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, seq_len=32)
    cell.limits = LIMITS
    return cell


def run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(), CPU,
                            log=lambda *a, **k: None)


def test_the_pod_cell_resolves():
    cell = harness.resolve(CELL)
    assert cell.chips == 4 and cell.program["mesh"] == {"pod": 2, "data": 2, "model": 1}
    assert harness.kind_module(cell).__name__ == "bench_train_pods"
    assert set(cell.limits) == set(LIMITS)
    names = {m["name"] for m in cell.per_layer}
    assert {"pod_exchange_ms", "pod_collective_ms", "data_ms", "device_idle_share",
            "device_busy_ms", "step_mfu"} == names
    one_chip = {m["name"] for m in harness.resolve("granite-L4.train-4k").per_layer}
    assert not one_chip & {"pod_exchange_ms", "pod_collective_ms"}


def test_sound_run_is_correct():
    out = run(small_cell())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 3 and out["failed"] == 0
    assert set(out["checks"]) == set(LIMITS)


def mean_first(monkeypatch):
    """The pod mean taken before the filter, as a step whose backward pass
    reduces over every batch axis does."""
    from repro.train import train_step

    sync = train_step.sync_gradients

    def fault(g, r, cfg, *, axis, n_pods):
        return sync(jax.tree.map(lambda x: jax.lax.pmean(x, axis), g), r, cfg,
                    axis=axis, n_pods=n_pods)

    monkeypatch.setattr(train_step, "sync_gradients", fault)


def no_exchange(monkeypatch):
    """Each pod steps on its own gradient."""
    from repro.train import train_step

    monkeypatch.setattr(train_step, "sync_gradients",
                        lambda g, r, cfg, *, axis, n_pods: (g, r))


def residual_dropped(monkeypatch):
    from repro.train import train_step

    sync = train_step.sync_gradients

    def fault(g, r, cfg, *, axis, n_pods):
        return sync(g, jax.tree.map(jnp.zeros_like, r), cfg, axis=axis, n_pods=n_pods)

    monkeypatch.setattr(train_step, "sync_gradients", fault)


def half_batch(monkeypatch):
    """Half of each pod's rows left out."""
    from repro.train import train_step

    loss_fn = train_step.loss_fn

    def half(cfg, params, batch, *a, **k):
        return loss_fn(cfg, params, {n: x[: x.shape[0] // 2] for n, x in batch.items()},
                       *a, **k)

    monkeypatch.setattr(train_step, "loss_fn", half)


@pytest.mark.parametrize("fault", [mean_first, no_exchange, residual_dropped, half_batch],
                         ids=lambda f: f.__name__)
def test_broken_exchange_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run(small_cell())
    assert not out["correct"], out["checks"]


def test_step_hlo_is_the_compiled_pod_step():
    """The traced run's readers get the text of the step the trainer ran,
    taken with no compile; only the exchange and the loss mean cross pods."""
    from bench import hlo_groups

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name) if "backend_compile" in name else None)
    trainer, spans, _, steps = train_pods.set_up(small_cell(), SEED)
    before = len(compiles)
    text = train_pods.compiled_step(trainer, steps[-1], spans.batches[-1]).as_text()
    assert len(compiles) == before and len(steps) == 1
    over = hlo_groups.collectives_over(text, MESH, "pod")
    assert over
    crossing = train_pods.pod_collectives(text, MESH)
    assert len(crossing) == len(over)
    assert all(c.endswith(" pod_exchange") or " f32[] " in c for c in crossing), crossing
    for line in text.splitlines():
        name = line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
        if name in over and "/pod_exchange/" not in line:
            assert " f32[] " in line, line


# ---------------------------------------------------------------------------
# the readers of the pod exchange
# ---------------------------------------------------------------------------

# device ids are ((pod * 2) + data): {0,2} and {1,3} span pod, {0,1} data
HLO = """\
HloModule jit_core, is_scheduled=true

ENTRY %main.9 (x: f32[64]) -> f32[64] {
  %x = f32[64]{0} parameter(0)
  %fusion.1 = f32[64]{0} fusion(%x), kind=kLoop, calls=%f.1, metadata={op_name="jit(core)/shard_map/pod_exchange/mul"}
  %all-reduce-start.3 = f32[64]{0} all-reduce-start(%fusion.1), channel_id=1, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(core)/shard_map/pod_exchange/psum"}
  %all-reduce-done.3 = f32[64]{0} all-reduce-done(%all-reduce-start.3), metadata={op_name="jit(core)/shard_map/pod_exchange/psum"}
  %all-reduce.4 = f32[] all-reduce(%x), channel_id=2, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(core)/shard_map/pmean"}
  %all-gather.5 = f32[64]{0} all-gather(%x), channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(core)/shard_map/attn/all-gather"}
  ROOT %fusion.6 = f32[64]{0} fusion(%all-reduce-done.3), kind=kLoop, calls=%f.2, metadata={op_name="jit(core)/adam/sub"}
}
"""
OP_S = {"fusion.1": 0.010, "all-reduce-start.3": 0.001, "all-reduce-done.3": 0.003,
        "all-reduce.4": 0.0005, "all-gather.5": 0.007, "fusion.6": 0.002}


def reader_ctx(op_s, hlo=HLO, name="pods-readers-test"):
    scopes._PARSED.pop(name, None)
    summary = trace.Summary(window_s=1.0, steps=2, busy_s=0.5, span_s={}, span_count={},
                            op_s=op_s, gaps=[], n_devices=4)
    return types.SimpleNamespace(summary=summary, cell=types.SimpleNamespace(name=name),
                                 mesh_shape=MESH, step_hlo=lambda: hlo)


def test_pod_readers_read_the_exchange_and_the_collectives_over_pod():
    ctx = reader_ctx(OP_S)
    # the filter's fusion and the exchange's async all-reduce, per step
    assert harness.reader("pod_exchange_ms").read(ctx) == pytest.approx(7.0)
    # every collective over pod, the loss mean's scalar among them, and not
    # the all-gather over data
    assert harness.reader("pod_collective_ms").read(ctx) == pytest.approx(2.25)


def test_pod_readers_report_nothing_without_the_exchange():
    plain = "\n".join(line for line in HLO.splitlines()
                      if "pod_exchange" not in line and "all-reduce" not in line)
    ctx = reader_ctx({"all-gather.5": 0.007, "fusion.6": 0.002}, hlo=plain, name="no-pods")
    assert harness.reader("pod_exchange_ms").read(ctx) is None
    assert harness.reader("pod_collective_ms").read(ctx) is None


def test_residual_gap_is_the_worst_leaf_over_pods():
    ref = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 2.0, "c": 3.0},
           "change_norms": {"a": 1.0, "b": 2.0, "c": 3.0},
           "residual_norms": [{"a": 1.0, "b": 2.0, "c": 0.0},
                              {"a": 1.0, "b": 2.0, "c": 0.0}]}
    side = dict(ref, residual_norms=[{"a": 1.0, "b": 2.0, "c": 0.0},
                                     {"a": 1.1, "b": 2.0, "c": 0.1}])
    found = train_pods.numbers(side, ref)
    # leaf a of pod 1: 0.1 / max(1.0, median 1.0); leaf c: 0.1 / median 1.0
    assert found["residual_gap"] == pytest.approx(0.1)
    assert train_pods.numbers(dict(side, residual_norms=side["residual_norms"][:1]),
                              ref)["residual_gap"] == float("inf")
