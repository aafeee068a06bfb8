"""Per-layer device times from the program's named scopes: the op_name
paths, the HLO parse, the readers over a hand-made trace summary, and the
scopes of a tiny granite-shaped train step compiled on the CPU."""

import dataclasses
import re
import types

import jax
import pytest

from bench import harness, scopes, trace


@pytest.mark.parametrize("op_name, scope", [
    ("jit(core)/jvp()/while/body/closed_call/attn/dot_general", "attn"),
    ("jit(core)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/dot_general", "attn"),
    ("jit(core)/jvp(logits)/reduce_max", "logits"),
    ("jit(core)/transpose(jvp(logits))/jit(take_along_axis)/scatter-add", "logits"),
    ("jit(core)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(core)/jvp()/while/body/closed_call/moe_router/jit(_one_hot)/eq", "moe_router"),
    ("jit(core)/adam/mul", "adam"),
    ("jit(core)/shard_map/pod_exchange/psum", "pod_exchange"),
    # the innermost known scope wins
    ("jit(core)/pod_exchange/adam/sqrt", "adam"),
    ("jit(core)/jvp()/while/body/closed_call/mul", None),
    ("jit(core)/transpose(jvp())/while", None),
    ("jit(attention)/moe/dot_general", None),
    ("", None),
])
def test_scope_of_reads_the_innermost_known_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """\
HloModule jit_core, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused.8 (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %mul.2 = f32[8]{0} multiply(%q, %q), metadata={op_name="jit(core)/transpose(jvp())/while/body/moe_dispatch/mul"}
  ROOT %scatter.3 = f32[8]{0} scatter(%mul.2), to_apply=%region_0.1, metadata={op_name="jit(core)/transpose(jvp())/while/body/moe_dispatch/scatter-add"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.3, metadata={op_name="jit(core)/jvp()/while/body/closed_call/attn/dot_general" source_file="m.py" source_line=3}
  %fusion.4 = f32[8]{0} fusion(%fusion.7), kind=kCustom, calls=%fused.8
  %copy.9 = f32[8]{0} copy(%fusion.4)
  ROOT %tuple.5 = (s32[], f32[8]) tuple(%p, %copy.9), metadata={op_name="jit(core)/jvp()/while/body/closed_call"}
}

ENTRY %main.6 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.3 = (s32[], f32[8]) while(%x), condition=%cond.1, body=%body.2, metadata={op_name="jit(core)/jvp()/while"}
  ROOT %fusion.12 = f32[8]{0} fusion(%while.3), kind=kLoop, calls=%f.4, metadata={op_name="jit(core)/adam/sub"}
}
"""
SCOPED = {"mul.2": "moe_dispatch", "scatter.3": "moe_dispatch", "fusion.7": "attn",
          "fusion.4": "moe_dispatch", "fusion.12": "adam"}


def test_instruction_scopes_reads_every_computation():
    # fusion.4 has no metadata: it takes the scope of the body it calls
    assert scopes.instruction_scopes(HLO) == SCOPED


def test_instruction_scopes_take_the_names_of_another_compile():
    renumbered = re.sub(r"(fusion|scatter)\.(\d+)", r"\1.1\2", HLO)
    assert scopes.instruction_scopes(HLO, names_from=renumbered) == {
        re.sub(r"(fusion|scatter)\.(\d+)", r"\1.1\2", k): v for k, v in SCOPED.items()}
    # a compile that does not align instruction for instruction is not used
    assert scopes.instruction_scopes(HLO, names_from=HLO.split("ENTRY")[0]) == SCOPED


def ctx_for(op_s, steps=2, hlo=HLO, name="scopes-test"):
    calls = []

    def step_hlo():
        calls.append(jax.config.jax_compilation_cache_include_metadata_in_key)
        return hlo

    scopes._PARSED.pop(name, None)
    summary = trace.Summary(window_s=1.0, steps=steps, busy_s=0.5, span_s={},
                            span_count={}, op_s=op_s, gaps=[], n_devices=1)
    cell = types.SimpleNamespace(name=name)
    return types.SimpleNamespace(summary=summary, cell=cell, step_hlo=step_hlo), calls


def test_scope_ms_and_unscoped_ms_split_the_self_time():
    op_s = {"fusion.7": 0.030, "fusion.4": 0.010, "fusion.12": 0.004,
            "while.3": 0.002, "copy.9": 0.001, "copy-done.1": 0.001}
    ctx, calls = ctx_for(op_s)
    assert scopes.scope_ms(ctx, {"attn"}) == pytest.approx(15.0)
    assert scopes.scope_ms(ctx, {"moe_dispatch", "moe_combine"}) == pytest.approx(5.0)
    assert scopes.scope_ms(ctx, {"adam"}) == pytest.approx(2.0)
    # in the step but absent from the window: nothing ran there
    assert scopes.scope_ms(ctx, {"moe_router"}) is None
    assert scopes.unscoped_ms(ctx) == pytest.approx(2.0)
    # the seven readers together are the per-step self time
    total = sum(harness.reader(m).read(ctx) or 0.0 for m in (
        "attn_ms", "moe_router_ms", "moe_dispatch_ms", "moe_experts_ms",
        "logits_ms", "adam_ms", "unscoped_ms"))
    assert total == pytest.approx(sum(op_s.values()) / 2 * 1e3)
    # the readers of one run share one parse of two compiles: as the cache
    # holds the step, then keyed with its metadata
    assert calls == [False, True]
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


def test_a_step_without_scopes_reports_nothing():
    plain = re.sub(r"metadata=\{[^}]*\}", "", HLO)
    ctx, _ = ctx_for({"fusion.7": 0.03, "while.3": 0.002}, hlo=plain, name="plain")
    for m in ("attn_ms", "moe_dispatch_ms", "adam_ms", "unscoped_ms"):
        assert harness.reader(m).read(ctx) is None


# ---------------------------------------------------------------------------
# the program's own scopes
# ---------------------------------------------------------------------------


def tiny_granite_step_hlo() -> str:
    """HLO text of a granite-shaped MoE train step (2 layers, d 64, 4
    experts top-2, vocab 256, seq 64, remat) compiled on one CPU device."""
    from repro.configs.base import MoEConfig, ShapeSpec
    from repro.configs.registry import get_config
    from repro.dist.collectives import SyncConfig
    from repro.launch.mesh import AXES, make_mesh
    from repro.train import train_step as ts

    cfg = dataclasses.replace(
        get_config("granite-moe-3b-a800m"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=32, vocab_size=256, remat=True,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=32, capacity_factor=1.25))
    mesh = make_mesh((1, 1, 1), AXES, devices=jax.devices()[:1])
    tcfg = ts.TrainConfig(sync=SyncConfig(strategy="geococo", density=0.25))
    make_jit, _ = ts.build_train_step(cfg, mesh, tcfg)
    batch = ts.input_specs(cfg, ShapeSpec("tiny", 64, 4, "train"))
    return make_jit(batch).lower(
        ts.abstract_params(cfg), ts.abstract_opt_state(cfg, tcfg),
        ts.abstract_residuals(cfg, tcfg), batch).compile().as_text()


def test_every_matmul_scatter_and_gather_of_the_step_is_scoped():
    text = tiny_granite_step_hlo()
    op = re.compile(r"=\s*\S+\s+(dot|scatter|gather)\(")
    seen, unscoped = set(), []
    for line in text.splitlines():
        m = op.search(line)
        meta = m and scopes._OP_NAME.search(line)
        if meta:
            scope = scopes.scope_of(meta.group(1))
            seen.add(scope)
            if scope is None:
                unscoped.append(line.strip())
    assert not unscoped
    assert {"attn", "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "embed", "logits"} <= seen
    named = scopes.instruction_scopes(text)
    assert "adam" in named.values()
