"""The train step on two pods: each pod's gradient comes from its own rows
of the batch and crosses the ``pod`` axis only through the exchange.

A tiny granite (2 layers, 4 experts top-2) on ``pod=2 x data=2`` of 4
forced host devices, against a plain per-pod reference: the program's loss
on one device for each pod's rows, the exchange written out in numpy, and
AdamW."""

import dataclasses
import os
import re

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig
from repro.dist.collectives import SyncConfig
from repro.launch.hlo_cost import collectives_over
from repro.launch.mesh import AXES, make_mesh
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.train import train_step as ts
from repro.train.trainer import FaultInjected, Trainer, TrainerConfig

SEED = 2**31 + 7
SEQ, BATCH, PODS = 32, 4, 2
SYNC = SyncConfig(strategy="geococo", density=0.25, chunk=64, min_leaf_size=64)
OPTIM = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=8)


def tiny(capacity_factor=1.25):
    return dataclasses.replace(
        get_config("granite-moe-3b-a800m"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=32, vocab_size=256, remat=True,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=32,
                      capacity_factor=capacity_factor))


def mesh_of(pods, data=2):
    return make_mesh((pods, data, 1), AXES, devices=jax.devices()[: pods * data])


def trainer(cfg, mesh, sync=SYNC, steps=3, **run):
    tcfg = ts.TrainConfig(sync=sync, optim=OPTIM, compute_dtype=jnp.float32)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ, global_batch=BATCH,
                      seed=SEED)
    return Trainer(cfg, mesh, tcfg,
                   TrainerConfig(steps=steps, seed=SEED, log_every=100, **run), data)


def host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float64), jax.device_get(tree))


def exchange(grads, residuals, sync=SYNC):
    """The exchange per leaf, written out: each pod keeps, per chunk of its
    raveled g + r, the ``round(density x chunk)`` largest |.|; the synced
    gradient is the mean over pods of what each kept, and the rest is each
    pod's new residual.  Leaves under ``min_leaf_size`` are a plain mean."""
    k = round(sync.density * sync.chunk)
    synced, new_res = [], []
    for leaf in range(len(grads[0])):
        g = [grads[p][leaf] for p in range(PODS)]
        r = [residuals[p][leaf] for p in range(PODS)]
        if g[0].size < sync.min_leaf_size:
            synced.append(sum(g) / PODS)
            new_res.append(r)
            continue
        sent, kept = [], []
        for p in range(PODS):
            acc = (g[p] + r[p]).ravel()
            m = np.concatenate([acc, np.zeros((-acc.size) % sync.chunk)])
            m = m.reshape(-1, sync.chunk)
            top = np.argsort(-np.abs(m), axis=1, kind="stable")[:, :k]
            s = np.zeros_like(m)
            np.put_along_axis(s, top, np.take_along_axis(m, top, axis=1), axis=1)
            sent.append(s.ravel()[: acc.size].reshape(g[p].shape))
            kept.append((m - s).ravel()[: acc.size].reshape(g[p].shape))
        synced.append(sum(sent) / PODS)
        new_res.append(kept)
    return synced, [[new_res[i][p] for i in range(len(grads[0]))] for p in range(PODS)]


def reference(cfg, params, batches):
    """Per step: each pod's loss and gradient on its own rows (one device,
    float32), the exchange, then AdamW.  Returns the losses and, after each
    step, Adam's first moment, the parameters and each pod's residuals."""
    tdef = jax.tree.structure(params)
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: ts.loss_fn(cfg, p, b, jnp.float32)))
    opt = adamw_init(params, OPTIM)
    res = [[np.zeros(x.shape) for x in jax.tree.leaves(params)] for _ in range(PODS)]
    rows = BATCH // PODS
    out = {"losses": [], "m": [], "params": [], "residuals": []}
    for b in batches:
        per = [grad(params, {k: v[p * rows:(p + 1) * rows] for k, v in b.items()})
               for p in range(PODS)]
        out["losses"].append(float(np.mean([float(l) for l, _ in per])))
        g = [[np.asarray(x, np.float64) for x in jax.tree.leaves(gp)] for _, gp in per]
        synced, res = exchange(g, res)
        params, opt, _ = adamw_update(
            params, jax.tree.unflatten(tdef, [jnp.asarray(x, jnp.float32) for x in synced]),
            opt, OPTIM)
        out["m"].append(host(opt["m"]))
        out["params"].append(host(params))
        out["residuals"].append([jax.tree.unflatten(tdef, r) for r in res])
    return out


def close(a, b, rtol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=rtol * np.abs(y).max())


@pytest.fixture(scope="module")
def three_steps():
    """The program's state after each of 3 steps, and the batches it ate."""
    tr = trainer(tiny(), mesh_of(PODS))
    p0 = host(tr.params)
    batches = [tr.data.batch(n) for n in range(3)]
    states = []
    for n in range(1, 4):
        tr.run_cfg.steps = n
        tr.run()
        states.append({"m": host(tr.opt_state["m"]), "params": host(tr.params),
                       "residuals": host(tr.residuals)})
    return tr, p0, batches, states


def test_per_pod_step_matches_the_per_pod_reference(three_steps):
    tr, p0, batches, states = three_steps
    ref = reference(tr.model_cfg, jax.tree.map(jnp.float32, p0), batches)
    np.testing.assert_allclose([h["loss"] for h in tr.history], ref["losses"],
                               rtol=1e-5)
    for n, st in enumerate(states):
        close(st["m"], ref["m"][n], 1e-4)          # the synced gradients
        close(st["params"], ref["params"][n], 1e-5)
        for p in range(PODS):
            close(jax.tree.map(lambda r: r[p], st["residuals"]),
                  ref["residuals"][n][p], 1e-4)


def test_residuals_differ_between_pods(three_steps):
    _, _, _, states = three_steps
    r = jax.tree.leaves(states[0]["residuals"])
    assert all(x.shape[0] == PODS for x in r)
    big = [x for x in r if x[0].size >= SYNC.min_leaf_size]
    assert big and all(np.abs(x[0] - x[1]).max() > 0 for x in big)
    small = [x for x in r if x[0].size < SYNC.min_leaf_size]
    assert all(not x.any() for x in small)


@pytest.mark.parametrize("sync", [SyncConfig(strategy="geococo", density=1.0, chunk=64,
                                             min_leaf_size=64),
                                  SyncConfig(strategy="hier")],
                         ids=["geococo-d1.0", "hier"])
def test_dense_exchange_is_the_global_mean_step(sync):
    """With no filter the mean over pods of each pod's mean gradient is the
    global mean; capacity that drops nothing keeps the routing alike."""
    cfg = tiny(capacity_factor=4.0)
    pods = trainer(cfg, mesh_of(PODS), sync=sync).run()
    one = trainer(cfg, mesh_of(1, data=4), sync=SyncConfig(strategy="hier")).run()
    np.testing.assert_allclose([h["loss"] for h in pods], [h["loss"] for h in one],
                               rtol=1e-5)
    np.testing.assert_allclose([h["grad_norm"] for h in pods],
                               [h["grad_norm"] for h in one], rtol=1e-4)


def test_no_collective_over_pod_outside_the_exchange():
    """Compiled for the benchmark's layout (pod=2 x data=2, model=1): only
    the exchange and the scalar loss mean cross ``pod``."""
    cfg, mesh = tiny(), mesh_of(PODS)
    tr = trainer(cfg, mesh)
    batch = {k: jnp.asarray(v) for k, v in tr.data.batch(0).items()}
    text = tr.make_jit(batch).lower(tr.params, tr.opt_state, tr.residuals,
                                    batch).compile().as_text()
    in_exchange = 0
    for line in text.splitlines():
        if not collectives_over(line, dict(mesh.shape), "pod"):
            continue
        if "/pod_exchange/" in line:
            in_exchange += 1
        else:
            assert re.search(r"=\s*\w+\[\]\S*\s", line), line
    assert in_exchange


def test_pod_residuals_survive_checkpoint_and_rollback(tmp_path):
    cfg, mesh = tiny(), mesh_of(PODS)
    clean = trainer(cfg, mesh, steps=4)
    clean.run()
    faulty = trainer(cfg, mesh, steps=4, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2,
                     ckpt_async=False)
    fired = []

    def injector(step):
        if step == 3 and not fired:
            fired.append(step)
            raise FaultInjected("simulated device loss")

    faulty.run(fault_injector=injector)
    assert fired and faulty.rollbacks == 1
    close(host(faulty.residuals), host(clean.residuals), 1e-6)
    resumed = trainer(cfg, mesh, steps=4, ckpt_dir=str(tmp_path / "ckpt"))
    assert resumed.maybe_resume() and resumed.step_idx == 4
    close(host(resumed.residuals), host(faulty.residuals), 0)
    assert all(x.shape[0] == PODS for x in jax.tree.leaves(resumed.residuals))
