"""Training-plane integration: trainer loop, checkpoint restart, elastic
reshard, straggler mitigation, fault injection.  8 forced host devices."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import (
    available_steps,
    gc_incomplete,
    latest_step,
    restore,
    save,
)
from repro.configs.registry import get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist.collectives import SyncConfig
from repro.launch.mesh import make_small_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import TrainConfig
from repro.train.trainer import FaultInjected, StragglerMonitor, Trainer, TrainerConfig


def _mk_trainer(tmp_path, *, steps=8, sync="hier", mesh=None, seed=0,
                control=None):
    cfg = get_smoke_config("minitron-8b")
    mesh = mesh or make_small_mesh()
    tcfg = TrainConfig(
        sync=SyncConfig(strategy=sync, density=0.25, chunk=64, min_leaf_size=64),
        # fixed optimizer horizon: the LR schedule must not depend on how many
        # steps one particular (possibly interrupted) run executes
        optim=AdamWConfig(lr=1e-3, total_steps=8, warmup_steps=2),
    )
    run_cfg = TrainerConfig(
        steps=steps, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=4,
        ckpt_async=False, log_every=100, seed=seed,
    )
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=8, seed=seed)
    return Trainer(cfg, mesh, tcfg, run_cfg, data_cfg, control=control)


def test_loss_decreases_and_checkpoints(tmp_path):
    tr = _mk_trainer(tmp_path, steps=8)
    hist = tr.run()
    assert len(hist) == 8
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert available_steps(str(tmp_path / "ckpt")) == [4, 8]


def test_restart_resumes_identically(tmp_path):
    # run 8 steps straight through
    tr1 = _mk_trainer(tmp_path / "a", steps=8)
    h1 = tr1.run()
    # run 4 steps, "crash", resume a fresh trainer, run to 8
    tr2 = _mk_trainer(tmp_path / "b", steps=4)
    tr2.run()
    tr3 = _mk_trainer(tmp_path / "b", steps=8)
    assert tr3.maybe_resume()
    assert tr3.step_idx == 4
    h3 = tr3.run()
    # deterministic data + state restore => identical trajectory
    np.testing.assert_allclose(h1[-1]["loss"], h3[-1]["loss"], rtol=1e-4)


def test_fault_injection_rolls_back_and_replays(tmp_path):
    tr = _mk_trainer(tmp_path, steps=8)
    fired = {"n": 0}

    def injector(step):
        if step == 5 and fired["n"] == 0:
            fired["n"] += 1
            raise FaultInjected("simulated device loss")

    hist = tr.run(fault_injector=injector)
    assert fired["n"] == 1
    assert tr.step_idx == 8
    assert hist[-1]["loss"] < hist[0]["loss"]
    # the rollback rebuilt the step: step 5 (the first replayed) compiled
    assert (tr.rollbacks, tr.compiles) == (1, 2)
    assert [h["step"] for h in hist if h["compiled"]] == [1, 5]


def test_history_marks_the_compiled_step(tmp_path):
    tr = _mk_trainer(tmp_path, steps=4)
    hist = tr.run()
    assert [h["compiled"] for h in hist] == [True, False, False, False]
    assert (tr.compiles, tr.rollbacks) == (1, 0)
    assert all(h["data_s"] > 0 for h in hist)


def test_straggler_monitor_skips_the_compiled_step(tmp_path):
    tr = _mk_trainer(tmp_path, steps=3)
    dts = [h["dt"] for h in tr.run()]
    # seeded by step 2, then one EWMA update with step 3; step 1 compiled
    a = tr.monitor.alpha
    assert tr.monitor.ewma == pytest.approx((1 - a) * dts[1] + a * dts[2])


def test_profile_holds_the_trainer_spans(tmp_path):
    """Each step is a ``train`` profiler step holding its ``trainer.*``
    spans on the host plane, tagged with the step."""
    from jax.profiler import ProfileData

    tr = _mk_trainer(tmp_path, steps=2)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        tr.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
    events = [e for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:") for line in p.lines for e in line.events]
    steps = {dict(e.stats)["step_num"]: e for e in events if e.name == "train"}
    assert sorted(steps) == [0, 1]
    spans = {}
    for e in events:
        if e.name.startswith("trainer."):
            n = dict(e.stats)["step"]
            t = steps[n]
            assert t.start_ns <= e.start_ns
            assert e.start_ns + e.duration_ns <= t.start_ns + t.duration_ns
            spans.setdefault(n, set()).add(e.name)
    want = {"trainer.data", "trainer.put", "trainer.wait", "trainer.control"}
    assert spans[0] >= want | {"trainer.compile"} and "trainer.step" not in spans[0]
    assert spans[1] >= want | {"trainer.step"} and "trainer.compile" not in spans[1]


def test_elastic_reshard_across_meshes(tmp_path):
    """A checkpoint written on one mesh restores onto a different mesh and
    training continues — elastic scaling."""
    mesh_a = make_small_mesh((2, 2, 2))
    tr_a = _mk_trainer(tmp_path, steps=4, mesh=mesh_a)
    tr_a.run()
    # restore onto a single-pod 4-device mesh (different topology)
    mesh_b = make_small_mesh((2, 2), ("data", "model"))
    tr_b = _mk_trainer(tmp_path, steps=6, mesh=mesh_b)
    assert tr_b.maybe_resume()
    assert tr_b.step_idx == 4
    hist = tr_b.run()
    assert tr_b.step_idx == 6
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_checkpoint_atomicity(tmp_path):
    d = str(tmp_path / "ckpt")
    state = {"w": jnp.arange(16.0).reshape(4, 4), "step": jnp.asarray(3)}
    save(d, 3, state)
    # leave a fake interrupted save behind
    os.makedirs(os.path.join(d, "step_9.tmp"))
    assert latest_step(d) == 3          # tmp never visible
    assert gc_incomplete(d) == 1
    back = restore(d, 3, state)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(state["w"]))
    # idempotent double-restore
    back2 = restore(d, 3, back)
    np.testing.assert_array_equal(np.asarray(back2["w"]), np.asarray(state["w"]))


def test_straggler_monitor_damping():
    m = StragglerMonitor(threshold=1.5, sustain=3)
    assert not m.observe(1.0)
    # transient spike: suppressed
    assert not m.observe(5.0)
    assert not m.observe(5.0)
    assert not m.observe(1.0)
    # sustained: trips once
    trips = [m.observe(10.0) for _ in range(3)]
    assert trips[-1] and m.trips == 1


def test_straggler_triggers_replan_hook(tmp_path):
    from repro.control import ControlPlane, PlanChanged, TraceView

    lat = np.array([[0.0, 10.0, 14.0, 10.0], [10.0, 0.0, 10.0, 14.0],
                    [14.0, 10.0, 0.0, 10.0], [10.0, 14.0, 10.0, 0.0]])
    cp = ControlPlane(TraceView(lat))
    tr = _mk_trainer(tmp_path, steps=6, control=cp)
    tr.monitor = StragglerMonitor(threshold=0.0, sustain=1)  # trip every step
    tr.run()
    assert tr.monitor.trips >= 1
    forced = [e for e in cp.events if isinstance(e, PlanChanged)
              and e.reason.startswith("straggler@")]
    assert len(forced) >= 1


def test_synthetic_data_deterministic():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=4, seed=5)
    a = SyntheticLM(cfg).batch(7)
    b = SyntheticLM(cfg).batch(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # learnable structure: copy probability leaves repeated tokens
    toks = a["tokens"]
    repeats = (toks[:, 1:] == toks[:, :-1]).mean()
    assert repeats > 0.02
