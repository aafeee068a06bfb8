"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, fast memory overuse, programs that do not fit.  These
tests compile the kernels at real widths and the pod-exchange region of
the train step on a ``pod=2 x data=2`` mesh of described devices, and check
that the Pallas kernels and the pod collectives are in the compiled HLO.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.registry import get_config
from repro.dist.collectives import (
    SyncConfig,
    chunked_topk_exchange,
    shard_local_specs,
)
from repro.dist.sharding import param_specs
from repro.kernels.crdt_merge import ops as crdt
from repro.kernels.rglru_scan import ops as rglru
from repro.kernels.rwkv6_wkv import ops as wkv
from repro.kernels.whitedata_filter import ops as wd
from repro.launch.hlo_cost import collectives_over
from repro.launch.mesh import make_mesh
from repro.train.train_step import TrainConfig, _make_pod_step, abstract_params


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,n", [(1 << 20, 256), (1000, 100)])
def test_crdt_merge_compiles(one_chip, m, n):
    """A 1 GiB store slab of 1 KiB records, and an unaligned batch that
    must be padded to whole (8, 128) tiles."""
    val = jax.ShapeDtypeStruct((m, n), jnp.int32, sharding=one_chip)
    ver = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    hlo = _hlo(
        lambda a, ra, b, rb: crdt.crdt_merge(a, ra, b, rb, interpret=False),
        val, ver, val, ver,
    )
    assert "tpu_custom_call" in hlo


def test_whitedata_filter_compiles(one_chip):
    """One granite expert leaf: 40 experts x 1536 x 512, f32."""
    leaf = jax.ShapeDtypeStruct((40, 1536, 512), jnp.float32, sharding=one_chip)
    hlo = _hlo(
        lambda g, r: wd.whitedata_filter(g, r, 0.5, interpret=False), leaf, leaf
    )
    assert "tpu_custom_call" in hlo


def test_rwkv6_wkv_compiles(one_chip):
    """rwkv6-7b widths: 64 heads of head dim 64."""
    b, t, h, n = 1, 1024, 64, 64
    x = jax.ShapeDtypeStruct((b, t, h, n), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((h, n), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((b, h, n, n), jnp.float32, sharding=one_chip)
    hlo = _hlo(
        lambda r, k, v, w, u, s: wkv.wkv6(r, k, v, w, u, s, interpret=False),
        x, x, x, x, u, s,
    )
    assert "tpu_custom_call" in hlo


def test_rglru_scan_compiles(one_chip):
    """recurrentgemma-9b RG-LRU width 4096, batch 2."""
    b, t, d = 2, 1024, 4096
    x = jax.ShapeDtypeStruct((b, t, d), jnp.float32, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((b, d), jnp.float32, sharding=one_chip)
    hlo = _hlo(
        lambda a, bb, h0: rglru.rglru_scan(a, bb, h0, interpret=False), x, x, h0
    )
    assert "tpu_custom_call" in hlo


def test_pod_sync_compiles_with_pod_collectives(topo):
    """The geococo exchange in the pod region (manual over `pod`, GSPMD over
    `data`) over granite's real leaf shapes for one layer, on pod=2 x
    data=2, with a stand-in for the pod's gradient: each pod scales the
    parameters by its own row of the batch.  Each chip reduces its own half
    of an expert leaf over ``pod`` (20 of 40 experts: 7,680 rows of 2048)."""
    mesh = make_mesh((2, 2, 1), ("pod", "data", "model"), devices=topo.devices)
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m"), n_layers=1)
    tcfg = TrainConfig(sync=SyncConfig(strategy="geococo"))
    p_abs = abstract_params(cfg)
    specs = param_specs(p_abs, mesh, "geococo")
    step = _make_pod_step(
        mesh, tcfg, specs, shard_local_specs(p_abs, specs, dict(mesh.shape), tcfg.sync),
        lambda p, b: (b["w"][0], jax.tree.map(lambda x: x * b["w"][0], p)))
    params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(
            l.shape, jnp.float32, sharding=NamedSharding(mesh, s)
        ),
        p_abs, specs,
    )
    res = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(
            (2,) + l.shape, jnp.float32, sharding=NamedSharding(mesh, P("pod", *s))
        ),
        p_abs, specs,
    )
    batch = {"w": jax.ShapeDtypeStruct((2,), jnp.float32,
                                       sharding=NamedSharding(mesh, P("pod")))}
    text = _hlo(step, params, batch, res)
    pod = collectives_over(text, dict(mesh.shape), "pod")
    assert "all-reduce" in pod
    assert any("f32[7680,2048]" in line for line in text.splitlines()
               if collectives_over(line, dict(mesh.shape), "pod"))


def test_topk_exchange_compiles_without_scatter(topo):
    """The geococo filter on ``pod=2``, over one chip's shard of a granite
    expert leaf (7,680 rows of 2048, 205 kept a row), builds its mask with
    no scatter: the only sort left is the one ``top_k`` lowers to."""
    mesh = make_mesh((2, 2, 1), ("pod", "data", "model"), devices=topo.devices)
    exchange = jax.shard_map(
        lambda g, r: chunked_topk_exchange(g, r, axis="pod"),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        axis_names={"pod"}, check_vma=False,
    )
    leaf = jax.ShapeDtypeStruct((7680, 2048), jnp.float32,
                                sharding=NamedSharding(mesh, P()))
    text = _hlo(exchange, leaf, leaf)
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*? (sort|scatter)\(", text, re.M)
    assert [name for name, op in ops if op == "scatter"] == []
    sorts = [name for name, op in ops if op == "sort"]
    assert sorts and all(name.startswith("top_k") for name in sorts), sorts
    assert "all-reduce" in collectives_over(text, dict(mesh.shape), "pod")
