"""The benchmark finds every cell's files by name, counts model FLOPs as
by hand, and refuses to measure anything but a known TPU."""

import json
import os
import subprocess
import sys
import types

import pytest

from bench import flops, harness
from bench.kinds import train


def spec():
    return json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves_from_its_files():
    b = spec()
    for w in b["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.config_name == w["config"] and cell.chips == w["chips"]
        assert callable(harness.reference_module(cell).Reference)
        assert cell.per_layer, "every cell reports a per-layer metric"
        for m in cell.per_layer:
            assert callable(harness.reader(m["name"]).read)
        for name, lim in cell.limits.items():
            assert lim["limit"] >= 0, name
        assert callable(harness.kind_module(cell).run)
        train.model_config(cell.config)
    for c in b["configs"]:
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


def test_model_flops_match_the_hand_count():
    cfg = harness.resolve("granite-L4.train-4k").config
    # per layer: q, k, v, o projections, router, 8 of 40 experts; tied unembedding
    assert flops.active_matmul_params(cfg) == 4 * (1536 * 1536 * 2 + 1536 * 512 * 2
                                                   + 1536 * 40 + 8 * 3 * 1536 * 512
                                                   ) + 1536 * 49155
    assert flops.train_flops_per_token(cfg, 4096) / 1e9 == pytest.approx(1.21, abs=0.005)
    l8 = dict(cfg, num_hidden_layers=8)
    assert flops.active_matmul_params(l8) / 1e6 == pytest.approx(277.3, abs=0.05)
    assert flops.train_flops_per_token(l8, 4096) / 1e9 == pytest.approx(1.97, abs=0.005)


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_non_tpu_platform_is_refused(monkeypatch):
    import jax

    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [cpu] * 4)
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.device_info(1)
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.device_info(4)
    assert harness.device_info(1)["count"] == 1


def test_command_prints_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, str(harness.BENCH_DIR / "run.py"), "--workload",
         spec()["workloads"][0]["name"], "--seed", str(2**31 + 3), "--seconds", "1"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert "no TPU" in run.stderr
