"""A training cell on several pods: ``Trainer.run`` with the exchange
between pods, through set-up, window and check.

It runs as ``bench/kinds/train.py`` does, with that module's functions,
and checks besides what only pods have.  Each pod's gradient comes from
its own rows of the batch and crosses pods through the traffic's ``sync``
exchange, whose error-feedback residuals differ by pod.  So set-up also
keeps on the host the residuals after the checked steps; the reference
(``bench/references/granite_moe_pods.py``) is given the exchange and the
pod split; and ``residual_gap`` compares, for each pod, the norm of each
leaf of its residual with the reference's.

A traced run hands its readers the HLO text of the executable the window
ran, taken from the trainer before it is freed, and logs the step's
compiled peak and its collectives over ``pod``.  The run keys the compile
cache with metadata, so that executable carries this program's scopes.
"""

from __future__ import annotations

import contextlib
import gc
import math
import re
import sys
import threading
import time

import numpy as np

from bench import flops, harness, hlo_groups, scopes
from bench import trace as trace_mod
from bench.kinds import train


def pods(cell) -> int:
    return cell.program["mesh"]["pod"]


def residual_layout(cell):
    """The per-pod residuals' shapes as the program declares them; a
    program that keeps one residual for all pods cannot run the cell."""
    from repro.train import train_step as ts

    return ts.abstract_residuals(train.model_config(cell.config),
                                 train.train_config(cell), pods(cell))


def keep_steps(trainer) -> list:
    """The jitted steps ``trainer`` builds from here on, in order."""
    made = []
    make_jit = trainer.make_jit

    def record(batch):
        made.append(make_jit(batch))
        return made[-1]

    trainer.make_jit = record
    return made


def compiled_step(trainer, step, batch):
    """The executable ``trainer`` ran: its jitted ``step`` lowered again at
    the trainer's state, which JAX answers from the executable it holds,
    with no compile."""
    import jax.numpy as jnp

    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    return step.lower(trainer.params, trainer.opt_state, trainer.residuals,
                      batch).compile()


def pod_collectives(hlo: str, mesh_shape: dict[str, int]) -> list[str]:
    """Name, result shape and scope of each collective of the step whose
    replica groups span ``pod``: the traffic that crosses pods."""
    over = hlo_groups.collectives_over(hlo, mesh_shape, "pod")
    found = []
    for line in hlo.splitlines():
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        name = name.lstrip("%")
        if name in over:
            op = re.search(r'op_name="([^"]*)"', rest)
            shape = rest.split(f" {over[name]}", 1)[0]
            found.append(f"{name} {shape} {scopes.scope_of(op.group(1)) if op else None}")
    return found


@contextlib.contextmanager
def keyed_with_metadata():
    """JAX's persistent compile cache keyed with each program's metadata,
    as ``bench/scopes.py`` keys its second compile, for the whole block.
    The executable a run loads is then this program's own, with its scope
    names, and the step's text serves the readers both ways."""
    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        yield
    finally:
        jax.config.update(key, before)


def reference(cell, mode: str = "f32", rows: int | None = None):
    """The cell's plain reference (``mode="fp8"``: the control), with the
    expert capacity reckoned over ``rows`` sequences (default: one pod's
    share of the batch)."""
    t = cell.traffic
    share = cell.program["global_batch"] // pods(cell)
    return harness.reference_module(cell).Reference(
        cell.config, t["optimizer"], t["seq_len"], rows or share, t["sync"], pods(cell),
        mode=mode)


def residual_norms(residuals) -> list[dict[str, float]]:
    """Per pod, the norm of each leaf of its residual (leading pod axis)."""
    import jax

    n = jax.tree.leaves(residuals)[0].shape[0]
    return [train.leaf_norms(jax.tree.map(lambda r: r[p], residuals)) for p in range(n)]


def program_side(state: dict, b1: float) -> dict:
    return dict(train.program_side(state, b1),
                residual_norms=residual_norms(state["residuals"]))


def numbers(side: dict, ref: dict) -> dict[str, float]:
    """``train.numbers`` and ``residual_gap``: the worst leaf, over pods, of
    |norm of the residual - the reference's| / max(reference's, median
    leaf's)."""
    found = train.numbers(side, ref)
    ours, theirs = side["residual_norms"], ref["residual_norms"]
    found["residual_gap"] = (max(train.worst_leaf_gap(a, b) for a, b in zip(ours, theirs))
                             if len(ours) == len(theirs) else math.inf)
    return found


def set_up(cell, seed: int):
    """The trainer, its spans, what the check compares after the checked
    steps, and the jitted steps the trainer built."""
    import jax

    layout = residual_layout(cell)
    trainer = train.build_trainer(cell, seed)
    if (jax.tree.map(lambda r: r.shape, trainer.residuals)
            != jax.tree.map(lambda r: r.shape, layout)):
        raise ValueError("the trainer's residuals are not in the per-pod layout")
    steps = keep_steps(trainer)
    spans = train.instrument(trainer)
    state = train.set_up_steps(trainer)
    state["residuals"] = train.host_tree(trainer.residuals)
    return trainer, spans, state, steps


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell, seed: int, seconds: float, trace: bool, t_start: float, device: dict,
        log=print) -> dict:
    with keyed_with_metadata():
        return _run(cell, seed, seconds, trace, t_start, device, log)


def _run(cell, seed, seconds, trace, t_start, device, log) -> dict:
    import jax

    trainer, spans, state, steps = set_up(cell, seed)
    setup_s = time.perf_counter() - t_start
    checked = len(state["losses"])
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name) if "backend_compile" in name else None)
    if trace:
        win, summary = train.traced_window(trainer, spans, seconds,
                                           cell.traffic["trace_steps"])
    else:
        win, summary = train.run_window(trainer, spans, seconds), None
    log(f"set-up: {setup_s:.6f} s; window: {win['steps']} steps in "
        f"{win['seconds']:.6f} s, {len(compiles)} compiles inside it; device "
        f"step times (ms) {[round(h['dt'] * 1e3, 3) for h in trainer.history]}",
        file=sys.stderr)
    peak = train.memory_peak(trainer.mesh)
    mesh_shape = dict(trainer.mesh.shape)
    losses = [h["loss"] for h in trainer.history]
    consumed = spans.batches
    b1 = trainer.tcfg.optim.b1
    hlo = None
    if trace:
        compiled = compiled_step(trainer, steps[-1], consumed[-1])
        hlo = compiled.as_text()
        crossing = pod_collectives(hlo, mesh_shape)
        log(f"compiled step: peak {compiled.memory_analysis().peak_memory_in_bytes} "
            f"bytes; {len(crossing)} collectives over pod: {crossing}", file=sys.stderr)
        del compiled
    del trainer, spans, steps
    gc.collect()

    picked = list(range(checked))
    if win["steps"]:
        picked.append(checked + int(np.random.default_rng(seed).integers(win["steps"])))
    regen: dict = {}
    worker = threading.Thread(
        target=lambda: regen.update(b=train.regenerate(cell, seed, picked)))
    worker.start()
    t_ref = time.perf_counter()
    ref = reference(cell).train(seed, consumed[:checked])
    log(f"reference: {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    worker.join()
    found = numbers(program_side(state, b1), ref)
    found["data_mismatch"] = train.data_mismatch([consumed[s] for s in picked], regen["b"])
    failed = sum(not math.isfinite(x) for x in losses)
    found["window_nonfinite"] = sum(not math.isfinite(x) for x in losses[checked:])
    correct, checks = harness.judge(found, cell.limits)

    tokens_per_step = cell.program["global_batch"] * cell.traffic["seq_len"]
    out = {"correct": correct, "attempted": len(losses), "failed": failed}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    if trace:
        ctx = harness.ReaderContext(
            summary, cell, device,
            flops.train_flops_per_token(cell.config, cell.traffic["seq_len"]),
            tokens_per_step, mesh_shape, lambda: hlo)
        out["metrics"] = harness.read_per_layer(ctx)
        out["breakdown"] = trace_mod.breakdown(summary)
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        rate = win["steps"] * tokens_per_step / win["seconds"]
        out["metrics"] = {"train_tokens_per_s": {"value": rate, "unit": "tokens/s"},
                          "setup_s": {"value": setup_s, "unit": "s"}}
    out["device"] = dev
    out["checks"] = checks
    return out
