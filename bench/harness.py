"""Find a cell's files by name, check the device, run the cell.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json       sizes as run, source, deployment
    bench/traffic/<traffic>.json      the mix; its "kind" names the driver
    bench/kinds/<kind>.py             ``run(cell, seed, seconds, trace, ...)``
    bench/limits/<workload>.json      each compared number's limit and readings
    bench/metrics/<metric>.py         ``read(ctx) -> float | None``
    bench/references/<name>.py        plain reference named by the config
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from pathlib import Path
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """JAX found no TPU, fewer chips than the cell needs, or an unknown one."""


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def program(self) -> dict:
        return self.config["program"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    c = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return Cell(
        name=workload, chips=w["chips"], config_name=c["name"],
        config=_json(root / c["file"]),
        traffic_name=w["traffic"],
        traffic=_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=_json(BENCH_DIR / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def kind_module(cell: Cell):
    return load_module(BENCH_DIR / "kinds" / f"{cell.traffic['kind']}.py")


def reader(metric: str):
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py")


def reference_module(cell: Cell):
    return load_module(BENCH_DIR / "references" / f"{cell.config['reference']}.py")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def peaks_for(kind: str) -> dict:
    table = _json(BENCH_DIR / "peaks.json")
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def device_info(chips: int) -> dict:
    """The chips this run measures; refuses anything but enough TPUs of a
    kind in the peak table."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    kind = devs[0].device_kind
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "peaks": peaks_for(kind)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, unset, at ``<checkout>/.jax_cache``; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# what every kind of cell shares
# ---------------------------------------------------------------------------


def judge(found: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit."""
    checks = {k: {"value": found[k], "limit": limits[k]["limit"]} for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer reader is given: the traced window reduced
    (``bench.trace.Summary``), the cell, the device with its peaks, and the
    sizes that turn times into rates.  ``step_hlo()`` compiles the timed
    step again (from the cache) and returns its HLO text, for readers that
    match trace events to instructions; ``mesh_shape`` names its axes."""
    summary: object
    cell: Cell
    device: dict
    flops_per_token: float
    tokens_per_step: int
    mesh_shape: dict[str, int]
    step_hlo: Callable[[], str]


def read_per_layer(ctx: ReaderContext) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    metrics = {}
    for m in ctx.cell.per_layer:
        value = reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             device: dict, log=print) -> dict:
    """Set up, measure and check one run; the result line as a dict."""
    return kind_module(cell).run(cell, seed, seconds, trace, t_start, device, log)
