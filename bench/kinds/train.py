"""A training cell: ``Trainer.run`` through set-up, window and check.

The cell builds ``repro.train.trainer.Trainer`` as the training entry point
does and drives ``Trainer.run``: batches from the trainer's own
``SyntheticLM``, the jitted step, the loss read back each step.  The
benchmark wraps only the calls into those layers, with host spans on the
profiler's clock: ``bench.data`` around each batch, ``bench.step`` around
the step call, ``bench.readback`` from its return to the next batch.

Set-up drives the cell's first ``checked_steps`` steps through the same
call and keeps on the host what the check compares; the window then runs
on from there.  Once the window has closed and the peak memory has been
read, the program's state is freed and the plain reference follows the
checked steps from the same seed.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import tempfile
import threading
import time

import numpy as np

from bench import flops, harness, synthetic
from bench import trace as trace_mod

SPAN_DATA, SPAN_STEP, SPAN_READBACK = "bench.data", "bench.step", "bench.readback"


class WindowClosed(Exception):
    """Raised into ``Trainer.run`` when it asks for a batch past the window."""


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the sizes the config file states."""
    from repro.configs.base import MoEConfig
    from repro.configs.registry import get_config

    prog = cfg["program"]
    if (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["logits_scaling"]) != (1.0, 1.0, 1.0):
        raise ValueError("the program has no embedding, residual or logits "
                         "multiplier: the config must state 1.0 for each")
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    if not math.isclose(cfg["attention_multiplier"], hd ** -0.5):
        raise ValueError("the program scales attention by 1/sqrt(head_dim)")
    return dataclasses.replace(
        get_config(prog["arch"]),
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], remat=prog["remat"],
        moe=MoEConfig(n_experts=cfg["num_local_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["intermediate_size"],
                      capacity_factor=cfg["capacity_factor"]),
    )


def mesh_for(cell):
    from repro.launch.mesh import AXES, make_mesh

    return make_mesh([cell.program["mesh"][a] for a in AXES], AXES)


def train_config(cell):
    import jax.numpy as jnp

    from repro.dist.collectives import SyncConfig
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainConfig

    prog = cell.program
    if (prog["param_dtype"], prog["compute_dtype"]) != ("float32", "bfloat16"):
        raise ValueError("cells run float32 parameters with bfloat16 compute")
    return TrainConfig(sync=SyncConfig(**cell.traffic["sync"]),
                       optim=AdamWConfig(**cell.traffic["optimizer"]),
                       param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)


def build_trainer(cell, seed: int):
    """``Trainer`` for the cell, parameters made on the device from ``seed``."""
    from repro.data.pipeline import DataConfig
    from repro.train.trainer import Trainer, TrainerConfig

    t = cell.traffic
    data = DataConfig(vocab_size=cell.config["vocab_size"], seq_len=t["seq_len"],
                      global_batch=cell.program["global_batch"], seed=seed, **t["data"])
    return Trainer(model_config(cell.config), mesh_for(cell), train_config(cell),
                   TrainerConfig(steps=t["checked_steps"], seed=seed), data)


def step_hlo(cell) -> str:
    """HLO text of the cell's compiled train step, compiled from shapes."""
    from repro.configs.base import ShapeSpec
    from repro.train import train_step as ts

    mcfg, tcfg = model_config(cell.config), train_config(cell)
    make_jit, _ = ts.build_train_step(mcfg, mesh_for(cell), tcfg)
    batch = ts.input_specs(mcfg, ShapeSpec(cell.name, cell.traffic["seq_len"],
                                           cell.program["global_batch"], "train"))
    return make_jit(batch).lower(
        ts.abstract_params(mcfg, tcfg.param_dtype), ts.abstract_opt_state(mcfg, tcfg),
        ts.abstract_residuals(mcfg, tcfg), batch).compile().as_text()


class Spans:
    """The benchmark's host spans, and the end of the window.

    ``batch`` stands in for the trainer's data object: it keeps a copy of
    every batch handed out and raises :class:`WindowClosed` once the
    deadline has passed or ``max_batches`` batches have been served."""

    def __init__(self, data):
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        self.data = data
        self.batches: list[dict[str, np.ndarray]] = []
        self.deadline = math.inf
        self.max_batches = None
        self.closed_at = None
        self._readback = None

    def end_readback(self):
        if self._readback is not None:
            self._readback.__exit__(None, None, None)
            self._readback = None

    def batch(self, step: int):
        self.end_readback()
        now = time.perf_counter()
        if now >= self.deadline or len(self.batches) == self.max_batches:
            self.closed_at = now
            raise WindowClosed
        with self._annotation(SPAN_DATA):
            out = self.data.batch(step)
        self.batches.append({k: np.array(v, copy=True) for k, v in out.items()})
        return out

    def wrap_step(self, step_fn):
        def spanned(*args):
            with self._annotation(SPAN_STEP):
                out = step_fn(*args)
            self._readback = self._annotation(SPAN_READBACK)
            self._readback.__enter__()
            return out
        return spanned


def instrument(trainer) -> Spans:
    spans = Spans(trainer.data)
    trainer.data = spans
    make_jit = trainer.make_jit
    trainer.make_jit = lambda batch: spans.wrap_step(make_jit(batch))
    return spans


def host_tree(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def set_up_steps(trainer) -> dict:
    """Drive the checked steps through ``Trainer.run``, keeping on the host
    what the check compares: the parameters before the first update, the
    optimizer's first moment after it, the parameters after the last."""
    checked = trainer.run_cfg.steps
    p0 = host_tree(trainer.params)
    trainer.run_cfg.steps = 1
    trainer.run()
    m1 = host_tree(trainer.opt_state["m"])
    trainer.run_cfg.steps = checked
    trainer.run()
    return {"p0": p0, "m1": m1, "p_last": host_tree(trainer.params),
            "losses": [h["loss"] for h in trainer.history[:checked]]}


def run_window(trainer, spans: Spans, seconds: float, max_steps=None) -> dict:
    """Drive ``Trainer.run`` until ``seconds`` have passed (or ``max_steps``
    steps); returns the steps completed and the window's wall time."""
    first = trainer.step_idx
    spans.max_batches = None if max_steps is None else len(spans.batches) + max_steps
    trainer.run_cfg.steps = 1 << 62
    t0 = time.perf_counter()
    spans.deadline = t0 + seconds
    try:
        trainer.run()
    except WindowClosed:
        pass
    finally:
        spans.end_readback()
    return {"steps": trainer.step_idx - first, "seconds": spans.closed_at - t0}


def traced_window(trainer, spans, seconds, trace_steps):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            win = run_window(trainer, spans, seconds, max_steps=trace_steps)
        finally:
            jax.profiler.stop_trace()
        tr = trace_mod.load(tdir)
    lo, hi = trace_mod.window_bounds(tr)
    return win, trace_mod.summarize(tr, lo, hi, win["steps"])


def memory_peak(mesh) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no statistics, as the CPU does)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in mesh.devices.flat)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def leaf_norms(tree) -> dict[str, float]:
    import jax

    return {jax.tree_util.keystr(k): float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for k, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_norms(before, after) -> dict[str, float]:
    import jax

    flat_a = jax.tree_util.tree_flatten_with_path(after)[0]
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
                np.asarray(a, np.float64).ravel() - np.asarray(b, np.float64).ravel()))
            for (k, a), b in zip(flat_a, jax.tree.leaves(before))}


def program_side(state: dict, b1: float) -> dict:
    """Losses, first-gradient and change norms per leaf, as the program's
    state gives them: the optimizer receives g and keeps m1 = (1 - b1) g."""
    return {"losses": state["losses"],
            "grad_norms": {k: v / (1 - b1) for k, v in leaf_norms(state["m1"]).items()},
            "change_norms": change_norms(state["p0"], state["p_last"])}


def worst_leaf_gap(ours: dict[str, float], ref: dict[str, float], keep=None) -> float:
    """max over leaves of |ours - ref| / max(ref, median leaf of ref)."""
    if set(ours) != set(ref):
        return math.inf
    med = float(np.median(list(ref.values())))
    return max(abs(ours[k] - ref[k]) / max(ref[k], med)
               for k in ref if keep is None or keep(k))


def numbers(side: dict, ref: dict) -> dict[str, float]:
    """The compared numbers of one run against the reference.

    A leaf whose reference gradient is under a thousandth of the median
    leaf's moves under Adam by round-off alone and is left out of the
    change."""
    med_g = float(np.median(list(ref["grad_norms"].values())))
    moved = lambda k: ref["grad_norms"][k] >= 1e-3 * med_g
    loss = max(abs(a - b) / abs(b) for a, b in zip(side["losses"], ref["losses"]))
    return {"loss_gap": loss if math.isfinite(loss) else math.inf,
            "grad_gap": worst_leaf_gap(side["grad_norms"], ref["grad_norms"]),
            "update_gap": worst_leaf_gap(side["change_norms"], ref["change_norms"], moved)}


def data_mismatch(consumed: list[dict], regenerated: list[dict]) -> int:
    """Tokens and labels that differ between what the trainer consumed and
    the benchmark's generator."""
    bad = 0
    for a, b in zip(consumed, regenerated):
        for k in ("tokens", "labels"):
            if a[k].shape != b[k].shape:
                bad += max(a[k].size, b[k].size)
            else:
                bad += int(np.sum(a[k] != b[k]))
    return bad


def regenerate(cell, seed: int, steps: list[int]) -> list[dict]:
    t = cell.traffic
    p = synthetic.zipf_probs(cell.config["vocab_size"], t["data"]["theta"])
    return [synthetic.batch(s, seed=seed, vocab_size=cell.config["vocab_size"],
                            seq_len=t["seq_len"], global_batch=cell.program["global_batch"],
                            probs=p, **t["data"]) for s in steps]


def reference(cell, mode: str = "f32", rows: int | None = None):
    """The cell's plain reference (``mode="fp8"``: the control), with the
    expert capacity reckoned over ``rows`` sequences (default: the batch)."""
    t = cell.traffic
    return harness.reference_module(cell).Reference(
        cell.config, t["optimizer"], t["seq_len"], rows or cell.program["global_batch"],
        mode=mode)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell, seed: int, seconds: float, trace: bool, t_start: float, device: dict,
        log=print) -> dict:
    import jax

    trainer = build_trainer(cell, seed)
    spans = instrument(trainer)
    state = set_up_steps(trainer)
    setup_s = time.perf_counter() - t_start
    checked = len(state["losses"])
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name) if "backend_compile" in name else None)
    if trace:
        win, summary = traced_window(trainer, spans, seconds, cell.traffic["trace_steps"])
    else:
        win, summary = run_window(trainer, spans, seconds), None
    log(f"set-up: {setup_s:.6f} s; window: {win['steps']} steps in "
        f"{win['seconds']:.6f} s, {len(compiles)} compiles inside it; device "
        f"step times (ms) {[round(h['dt'] * 1e3, 3) for h in trainer.history]}",
        file=sys.stderr)
    peak = memory_peak(trainer.mesh)
    mesh_shape = dict(trainer.mesh.shape)
    losses = [h["loss"] for h in trainer.history]
    consumed = spans.batches
    b1 = trainer.tcfg.optim.b1
    del trainer, spans
    gc.collect()

    # the reference follows the checked steps from the seed while the
    # benchmark's generator regenerates what the trainer consumed: the
    # checked batches and one window batch drawn from the seed
    picked = list(range(checked))
    if win["steps"]:
        picked.append(checked + int(np.random.default_rng(seed).integers(win["steps"])))
    regen: dict = {}
    worker = threading.Thread(target=lambda: regen.update(b=regenerate(cell, seed, picked)))
    worker.start()
    ref = reference(cell).train(seed, consumed[:checked])
    worker.join()
    found = numbers(program_side(state, b1), ref)
    found["data_mismatch"] = data_mismatch([consumed[s] for s in picked], regen["b"])
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
            tokens_per_step, mesh_shape, lambda: step_hlo(cell))
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
