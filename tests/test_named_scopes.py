"""The named scopes of the train step (``attn``, ``moe_*``, ``embed``,
``logits``, ``adam``, ``pod_exchange``) change only the HLO metadata, and
the collectives over ``pod`` are the ones under ``pod_exchange``.
8 forced host devices."""

import contextlib
import dataclasses
import os
import re

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import pytest

from repro.configs.base import MoEConfig, ShapeSpec
from repro.configs.registry import get_config
from repro.dist.collectives import SyncConfig
from repro.launch.hlo_cost import classify_groups, collectives_over
from repro.launch.mesh import AXES, make_mesh
from repro.train import train_step as ts

MESH_SHAPE = {"pod": 2, "data": 2, "model": 2}
_METADATA = re.compile(r", metadata=\{[^}]*\}")
# the module's source tables, which the metadata's stack frames point into
_SOURCE_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCALAR_RESULT = re.compile(r"=\s*\w+\[\]\S*\s")


def tiny_step_hlo() -> str:
    """A granite-shaped MoE train step (2 layers, d 64, 4 experts top-2,
    vocab 256, seq 64, remat, geococo) compiled on pod=2 x data=2 x model=2."""
    cfg = dataclasses.replace(
        get_config("granite-moe-3b-a800m"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=32, vocab_size=256, remat=True,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=32, capacity_factor=1.25))
    mesh = make_mesh(tuple(MESH_SHAPE.values()), AXES)
    tcfg = ts.TrainConfig(sync=SyncConfig(strategy="geococo", density=0.25, chunk=64,
                                          min_leaf_size=64))
    make_jit, _ = ts.build_train_step(cfg, mesh, tcfg)
    batch = ts.input_specs(cfg, ShapeSpec("tiny", 64, 4, "train"))
    return make_jit(batch).lower(
        ts.abstract_params(cfg), ts.abstract_opt_state(cfg, tcfg),
        ts.abstract_residuals(cfg, tcfg, MESH_SHAPE["pod"]), batch).compile().as_text()


def strip_metadata(text: str) -> str:
    return _METADATA.sub("", _SOURCE_TABLES.sub("", text))


@pytest.fixture(scope="module")
def scoped_hlo():
    return tiny_step_hlo()


def test_scopes_change_only_metadata(scoped_hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = tiny_step_hlo()
    assert "pod_exchange" not in plain and "pod_exchange" in scoped_hlo
    assert strip_metadata(plain) == strip_metadata(scoped_hlo)


def test_the_pod_exchange_collectives_are_its_scope(scoped_hlo):
    """Each pod takes its gradient from its own rows, so every collective
    over ``pod`` larger than a scalar lies in ``pod_exchange`` and spans
    ``pod`` alone; the loss mean is the one scalar that may lie outside."""
    in_scope = 0
    for line in scoped_hlo.splitlines():
        if not collectives_over(line, MESH_SHAPE, "pod"):
            continue
        path = _OP_NAME.search(line).group(1)
        assert classify_groups(line, MESH_SHAPE)[0] == {"pod"}, line
        if "/pod_exchange/" in path:
            in_scope += 1
        else:
            assert _SCALAR_RESULT.search(line), line
    assert in_scope
