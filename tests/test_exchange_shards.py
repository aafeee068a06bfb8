"""The pod exchange on each chip's own shard of a leaf
(``collectives.shard_local_specs``): which leaves qualify, on granite's real
shapes and on a tiny granite, and that the train step built with the plan
is the step with every leaf whole, to the bit, without the whole leaf's
gathers.  A tiny granite on ``pod=2 x data=2`` of forced host devices."""

import dataclasses
import os
import re
import types

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig
from repro.dist.collectives import SyncConfig, exchange_local_share, shard_local_specs
from repro.dist.sharding import param_specs
from repro.launch.hlo_cost import classify_groups
from repro.launch.mesh import AXES, make_mesh
from repro.optim.adamw import AdamWConfig
from repro.train import train_step as ts
from repro.train.trainer import Trainer, TrainerConfig

SEED = 2**31 + 23
GRANITE_SYNC = SyncConfig(strategy="geococo", density=0.1, chunk=2048, min_leaf_size=4096)
# On the tiny granite below, with chunks of 256, the norms are replicated
# and the router's runs (32 rows x 4 experts) are not whole chunks: it is
# filtered whole (512 elements).  At a min_leaf_size of 4096 wk's and wv's
# shards (2,048) fall under it, their whole leaves (4,096) do not, and the
# router is a plain mean.  The other leaves are filtered on their shard.
MISALIGNED = dict(density=0.25, chunk=256, min_leaf_size=256)
UNDER_MIN = dict(density=0.25, chunk=256, min_leaf_size=4096)
ON_SHARD = {"['embed']['table']", "['ffn']['wg']", "['ffn']['wi']", "['ffn']['wo']",
            "['mixer']['wq']", "['mixer']['wo']"}
REPLICATED = {"['final_norm']", "['norm1']", "['norm2']"}
KINDS = {
    "misaligned": {"on its shard": ON_SHARD | {"['mixer']['wk']", "['mixer']['wv']"},
                   "whole, sharded": {"['ffn']['router']"}, "replicated": REPLICATED},
    "under-min": {"on its shard": ON_SHARD, "replicated": REPLICATED,
                  "whole, sharded": {"['ffn']['router']", "['mixer']['wk']",
                                     "['mixer']['wv']"}},
}
CASES = {"misaligned": SyncConfig(strategy="geococo", **MISALIGNED),
         "under-min": SyncConfig(strategy="geococo", **UNDER_MIN)}
_SHAPE = re.compile(r"\b[a-z]\w*\[([\d,]*)\]")
_COLLECTIVE = re.compile(r"=\s*(.*?)\s(all-gather|all-reduce)(?:-start)?\(")


def granite_l8():
    return dataclasses.replace(get_config("granite-moe-3b-a800m"), n_layers=8)


def tiny():
    return dataclasses.replace(
        get_config("granite-moe-3b-a800m"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=32, vocab_size=256, remat=True,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=32, capacity_factor=1.25))


def by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def plan_of(cfg, mesh_shape, sync):
    """(abstract params, their in-pod specs, the exchange's plan)."""
    shape = dict(zip(AXES, mesh_shape))
    params = ts.abstract_params(cfg)
    specs = param_specs(params, types.SimpleNamespace(shape=shape), sync.strategy)
    return params, specs, shard_local_specs(params, specs, shape, sync)


def tiny_kinds(sync) -> dict[str, set]:
    """The tiny granite's leaves by what the plan makes of them."""
    leaves, specs, plan = map(by_path, plan_of(tiny(), (2, 2, 1), sync))
    out: dict[str, set] = {}
    for k in leaves:
        name = re.sub(r"\['scan'\]\[0\]|\['w'\]|\['g'\]", "", k)
        sharded = any(part for part in specs[k])
        out.setdefault("on its shard" if plan[k] != P() else
                       "whole, sharded" if sharded else "replicated", set()).add(name)
    return out


# ---------------------------------------------------------------------------
# the plan on granite-moe-3b-a800m at 8 layers (shapes only)
# ---------------------------------------------------------------------------


def test_granite_data_sharded_leaves_are_filtered_on_their_shard():
    params, specs, plan_tree = plan_of(granite_l8(), (2, 2, 1), GRANITE_SYNC)
    leaves, specs, plan = by_path(params), by_path(specs), by_path(plan_tree)
    local = {k for k, s in plan.items() if s != P()}
    assert local == {k for k, s in specs.items() if "data" in s}
    kinds = {re.sub(r"\['scan'\]\[0\]", "", k) for k in local}
    assert kinds == {"['ffn']['router']['w']", "['ffn']['wg']", "['ffn']['wi']",
                     "['ffn']['wo']", "['mixer']['wq']['w']", "['mixer']['wk']['w']",
                     "['mixer']['wv']['w']", "['mixer']['wo']['w']"}
    assert {k for k in leaves if k not in local} == {
        "['embed']['table']", "['final_norm']['g']", "['scan'][0]['norm1']['g']",
        "['scan'][0]['norm2']['g']"}
    assert leaves["['embed']['table']"].shape[0] % 2  # 49,155 rows: never split
    assert round(exchange_local_share(params, plan_tree), 3) == 0.914
    assert sum(leaves[k].size for k in local) == 805_797_888


@pytest.mark.parametrize("mesh_shape", [(2, 2, 2), (2, 1, 2)], ids=["data2-model2", "model2"])
def test_granite_model_sharded_last_dim_falls_back_to_whole(mesh_shape):
    """``model`` splits every sharded leaf's last dim, whose runs (the last
    dim over 2: 256, 768 or 20) are not whole chunks of 2048."""
    params, specs, plan = plan_of(granite_l8(), mesh_shape, GRANITE_SYNC)
    assert any("model" in s for s in jax.tree.leaves(specs))
    assert all(s == P() for s in jax.tree.leaves(plan))
    assert exchange_local_share(params, plan) == 0


@pytest.mark.parametrize("shape, spec, data, chunk, min_size, local", [
    ((8, 40, 1536, 512), P(None, "data", None, None), 2, 2048, 4096, True),
    ((8, 1536, 40), P(None, "data", None), 2, 2048, 4096, True),   # runs of 15 chunks
    ((8, 1536, 40), P(None, "data", None), 2, 4096, 4096, False),  # 30,720 % 4,096
    ((8, 1536, 1536), P(None, "data", "model"), 2, 2048, 4096, False),  # runs of 768
    ((8, 1536, 4096), P(None, "data", "model"), 2, 2048, 4096, True),   # runs of 2,048
    ((64, 64), P("data", None), 2, 64, 2048, True),                # shard 2,048
    ((64, 64), P("data", None), 2, 64, 2049, False),               # shard under the min
    ((64, 64), P(), 2, 64, 0, False),                              # replicated
    ((64, 64), P("data", None), 1, 64, 0, False),                  # one shard
], ids=["experts", "router", "router-chunk4096", "model-last-dim", "model-aligned",
        "at-min", "under-min", "replicated", "one-shard"])
def test_shard_local_specs_rule(shape, spec, data, chunk, min_size, local):
    mesh_shape = {"pod": 2, "data": data, "model": 2}
    sync = SyncConfig(strategy="geococo", chunk=chunk, min_leaf_size=min_size)
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    got = shard_local_specs({"w": leaf}, {"w": spec}, mesh_shape, sync)["w"]
    assert got == (spec if local else P())


# ---------------------------------------------------------------------------
# the tiny pod step: the plan against every leaf whole
# ---------------------------------------------------------------------------


def trainer(sync, mesh_shape=(2, 2, 1)):
    cfg = tiny()
    mesh = make_mesh(mesh_shape, AXES, devices=jax.devices()[: int(np.prod(mesh_shape))])
    tcfg = ts.TrainConfig(sync=sync, compute_dtype=jnp.float32,
                          optim=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=8))
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4, seed=SEED)
    return Trainer(cfg, mesh, tcfg, TrainerConfig(steps=3, seed=SEED, log_every=100), data)


def every_leaf_whole(leaves, specs, mesh_shape, cfg):
    return jax.tree.map(lambda s: P(), specs)


def state_after_three_steps(sync):
    tr = trainer(sync)
    tr.run()
    return tr, jax.device_get((tr.params, tr.opt_state, tr.residuals))


@pytest.mark.parametrize("case, sync", [
    ("misaligned", CASES["misaligned"]),
    ("under-min", CASES["under-min"]),
    ("misaligned", SyncConfig(strategy="geococo", ring_order=(1, 0), **MISALIGNED)),
    ("misaligned", SyncConfig(strategy="hier", **MISALIGNED)),
], ids=["geococo-misaligned", "geococo-under-min", "geococo-relay-ring", "hier"])
def test_shard_local_exchange_is_the_whole_leaf_exchange_to_the_bit(case, sync,
                                                                     monkeypatch):
    assert tiny_kinds(sync) == KINDS[case]

    tr, planned = state_after_three_steps(sync)
    assert 0.8 < tr.exchange_local_share < 1
    monkeypatch.setattr(ts, "shard_local_specs", every_leaf_whole)
    whole_tr, whole = state_after_three_steps(sync)
    assert whole_tr.exchange_local_share == 0
    a, b = jax.tree.leaves(planned), jax.tree.leaves(whole)
    assert len(a) == len(b) and jax.tree.structure(planned) == jax.tree.structure(whole)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    if sync.needs_residuals:
        assert any(np.abs(r).max() > 0 for r in jax.tree.leaves(planned[2]))


def test_one_pod_has_no_plan():
    tr = trainer(CASES["misaligned"], mesh_shape=(1, 2, 1))
    assert tr.shardings["exchange"] is None and tr.exchange_local_share == 0


def exchange_collectives(hlo: str, mesh_shape: dict) -> dict[str, list[tuple]]:
    """The result shapes of the all-gathers over ``data`` and the all-reduces
    over ``pod`` in the ``pod_exchange`` scope, the loss mean's scalar left
    out."""
    found = {"all-gather": [], "all-reduce": []}
    for line in hlo.splitlines():
        m = _COLLECTIVE.search(line)
        if not m or "/pod_exchange/" not in line:
            continue
        over = {"all-gather": {"data"}, "all-reduce": {"pod"}}[m.group(2)]
        if classify_groups(line, mesh_shape)[0] == over:
            found[m.group(2)] += [tuple(int(d) for d in s.split(",")) for s in
                                  _SHAPE.findall(m.group(1)) if s]
    return found


@pytest.mark.parametrize("case", list(CASES))
def test_shard_local_leaves_are_neither_gathered_nor_reduced_whole(case):
    """Compiled on pod=2 x data=2: a leaf filtered on its shard is gathered
    over ``data`` nowhere in the exchange, and what it reduces over ``pod``
    is its shard; every other leaf keeps the whole-leaf form."""
    sync = CASES[case]
    tr = trainer(sync)
    batch = {k: jnp.asarray(v) for k, v in tr.data.batch(0).items()}
    hlo = tr.make_jit(batch).lower(tr.params, tr.opt_state, tr.residuals,
                                   batch).compile().as_text()
    found = exchange_collectives(hlo, dict(tr.mesh.shape))

    leaves, specs, plan = map(by_path, plan_of(tiny(), (2, 2, 1), sync))
    local = {leaves[k].shape for k in leaves if plan[k] != P()}
    whole_sharded = {leaves[k].shape for k in leaves
                     if plan[k] == P() and any(part for part in specs[k])}
    assert local and whole_sharded and not local & whole_sharded
    gathered = set(found["all-gather"])
    assert gathered and gathered <= whole_sharded

    def reduced(k):
        """The elements a chip all-reduces for leaf ``k``: its shard or the
        whole leaf, padded to whole chunks where it is filtered."""
        n = leaves[k].size // (2 if plan[k] != P() else 1)
        return -(-n // sync.chunk) * sync.chunk if n >= sync.min_leaf_size else n

    assert sorted(int(np.prod(s)) for s in found["all-reduce"]) == sorted(
        reduced(k) for k in leaves)
