#!/usr/bin/env python3
"""Drive the device plane once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: database kernels + trainer
    python chip_smoke.py --chips 4    # four chips: the pod gradient exchange

One chip, two phases:

* kernels -- ``crdt_merge`` over two YCSB-shaped store slabs (2^20 records
  x 256 int32 lanes = 1 KiB per record, 1 GiB per side) and
  ``whitedata_filter`` over one granite expert leaf (40 x 1536 x 512 f32),
  both compiled (never interpreted) and bit-identical to their ``ref``.
* trainer -- granite-moe-3b-a800m at published widths, depth cut to 4
  layers, through ``Trainer`` -> ``build_train_step`` with geococo sync,
  fp32 parameters and Adam, seq 4096, and the largest global batch whose
  compiled step fits the chip.  Step 0's loss is checked against a float32
  evaluation at ``precision="highest"``; five steps must lower the loss.

``--chips 4`` runs the pod exchange on a ``pod=2 x data=2`` mesh and
nothing else: geococo at density 0.1 on granite at 8 layers, then flat
against geococo at density 1.0 (which is exactly a pmean) at 4 layers.

Phase results go to stdout.  The last line is one JSON object naming the
device.  Any failed check raises, so the exit code is non-zero; so is a run
that finds no TPU.  Data is random, made on the device from ``--seed``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import AXES, make_mesh  # noqa: E402

ARCH = "granite-moe-3b-a800m"
SEQ = 4096
STORE = (1 << 20, 256)       # records x int32 lanes: 1 KiB records
LEAF = (40, 1536, 512)       # granite's expert leaf: experts x d_model x d_expert
# The model computes in bf16 (8-bit significand, unit roundoff 2^-8) with
# f32 accumulation; the reference is f32 at precision="highest".  The loss
# is a mean over B x 4096 tokens, so per-element roundoff averages out: two
# bf16 units of the loss bound the difference.
LOSS_RTOL_BF16 = 2.0**-7
# geococo at density 1.0 exchanges exactly a pmean, as flat does, but the
# two partition the step differently (flat replicates the parameters and
# leaves activations unconstrained; geococo shards them over `data`), so
# sums are reassociated and bf16 roundings land at different points.  One
# bf16 unit (2^-8) bounds that for the losses and for the first step's
# gradient norm.  Later gradient norms are not compared: Adam moves every
# parameter by up to lr however small its gradient, which amplifies the
# last-bit differences.
FLAT_RTOL = 2.0**-8


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def compile_aot(jitted, *args):
    """Compile ``jitted`` for ``args`` ahead of time; (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def timed_run(compiled, *args):
    """One warm call after the first; returns (outputs, seconds)."""
    import jax

    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def memory_limit(device) -> int:
    return device.memory_stats()["bytes_limit"]


def fmt(xs) -> str:
    return "[" + ", ".join(f"{x:.6f}" for x in xs) + "]"


# ---------------------------------------------------------------------------
# phase (a): database kernels at store size
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> list[float]:
    """Returns the compile seconds of each ahead-of-time compile."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.crdt_merge import ops as crdt
    from repro.kernels.whitedata_filter import ops as wd

    k_val_a, k_val_b, k_ver_a, k_ver_b, k_g, k_r = jax.random.split(
        jax.random.PRNGKey(seed), 6
    )
    m, n = STORE
    i32 = jnp.iinfo(jnp.int32)

    @jax.jit
    def slabs():
        vals = [jax.random.randint(k, (m, n), i32.min, i32.max, jnp.int32)
                for k in (k_val_a, k_val_b)]
        # few distinct versions so that ties (kept from side a) occur
        vers = [jax.random.randint(k, (m,), 0, 1024, jnp.int32)
                for k in (k_ver_a, k_ver_b)]
        return vals[0], vers[0], vals[1], vers[1]

    args = jax.block_until_ready(slabs())
    compiled, c_merge = compile_aot(jax.jit(crdt.crdt_merge), *args)
    kernel_in_hlo = "tpu_custom_call" in compiled.as_text()
    (val, ver), run_s = timed_run(compiled, *args)
    ref_val, ref_ver = jax.jit(crdt.crdt_merge_ref)(*args)
    bad = int(jnp.sum(val != ref_val)) + int(jnp.sum(ver != ref_ver))
    log(f"kernel crdt_merge {m}x{n} int32 ({gib(m * n * 4)} per side): compile "
        f"{c_merge:.3f} s, run {run_s * 1e3:.3f} ms, tpu_custom_call "
        f"{kernel_in_hlo}, mismatches vs crdt_merge_ref {bad}")
    check(kernel_in_hlo, "crdt_merge compiled without its Pallas kernel")
    check(bad == 0, "crdt_merge differs from crdt_merge_ref")
    del args, val, ver, ref_val, ref_ver

    shape, tau = LEAF, 0.5
    g = jax.random.normal(k_g, shape, jnp.float32)
    r = 0.1 * jax.random.normal(k_r, shape, jnp.float32)
    compiled, c_filter = compile_aot(
        jax.jit(lambda g, r: wd.whitedata_filter(g, r, tau)), g, r)
    kernel_in_hlo = "tpu_custom_call" in compiled.as_text()
    out, run_s = timed_run(compiled, g, r)
    ref = jax.jit(lambda g, r: wd.whitedata_filter_ref(g, r, tau))(g, r)
    bad = [int(jnp.sum(a != b)) for a, b in zip(out, ref)]
    log(f"kernel whitedata_filter {shape} f32 tau={tau}: compile {c_filter:.3f} s, "
        f"run {run_s * 1e3:.3f} ms, tpu_custom_call {kernel_in_hlo}, kept "
        f"{int(out[2])} of {g.size}, mismatches vs whitedata_filter_ref "
        f"(send, residual, kept) {bad}")
    check(kernel_in_hlo, "whitedata_filter compiled without its Pallas kernel")
    check(bad == [0, 0, 0], "whitedata_filter differs from whitedata_filter_ref")
    log(f"kernels: peak_bytes_in_use {gib(peak_bytes(jax.devices()[0]))}")
    return [c_merge, c_filter]


# ---------------------------------------------------------------------------
# trainer helpers
# ---------------------------------------------------------------------------


def granite(n_layers: int):
    from repro.configs.registry import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=n_layers)
    log(f"model {ARCH}: published widths (d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, {cfg.moe.n_experts} "
        f"experts top-{cfg.moe.top_k} d_expert {cfg.moe.d_expert}, vocab "
        f"{cfg.vocab_size}); depth cut {full.n_layers} -> {n_layers} layers")
    return cfg


def train_config(sync):
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainConfig

    # full learning rate from the first update, so a few steps show learning
    return TrainConfig(sync=sync, optim=AdamWConfig(warmup_steps=1))


def compile_step_shapes(cfg, mesh, tcfg, batch: int):
    """Compile the train step at ``batch`` sequences from shapes alone
    (nothing allocated; the step's own in_shardings place them)."""
    from repro.configs.base import ShapeSpec
    from repro.train import train_step as ts

    make_jit, _ = ts.build_train_step(cfg, mesh, tcfg)
    batch_abs = ts.input_specs(cfg, ShapeSpec("smoke", SEQ, batch, "train"))
    return compile_aot(
        make_jit(batch_abs), ts.abstract_params(cfg, tcfg.param_dtype),
        ts.abstract_opt_state(cfg, tcfg),
        ts.abstract_residuals(cfg, tcfg, mesh.shape.get("pod", 1)),
        batch_abs,
    )


def make_trainer(cfg, mesh, tcfg, batch: int, steps: int, seed: int):
    from repro.data.pipeline import DataConfig
    from repro.train.trainer import Trainer, TrainerConfig

    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                      global_batch=batch, seed=seed)
    run = TrainerConfig(steps=steps, log_every=1, seed=seed)
    return Trainer(cfg, mesh, tcfg, run, data)


def compile_trainer_step(trainer):
    """Compile the trainer's own step for its arrays and first batch: the
    same program its first step then finds compiled; (compiled, seconds)."""
    import jax.numpy as jnp

    batch = {k: jnp.asarray(v) for k, v in trainer.data.batch(0).items()}
    return compile_aot(trainer.make_jit(batch), trainer.params,
                       trainer.opt_state, trainer.residuals, batch)


def run_trainer(trainer) -> list[dict]:
    import math

    t0 = time.perf_counter()
    hist = trainer.run()
    log(f"  {len(hist)} steps in {time.perf_counter() - t0:.3f} s")
    check(all(math.isfinite(h["loss"]) for h in hist), "non-finite loss")
    return hist


# ---------------------------------------------------------------------------
# phase (b): granite trainer on one chip
# ---------------------------------------------------------------------------


def phase_trainer(seed: int, steps: int = 5) -> list[float]:
    """Returns the compile seconds of each ahead-of-time compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.collectives import SyncConfig
    from repro.train.train_step import loss_fn

    dev = jax.devices()[0]
    limit = memory_limit(dev)
    cfg = granite(4)
    mesh = make_mesh((1, 1, 1), AXES)
    tcfg = train_config(SyncConfig(strategy="geococo"))

    # largest global batch whose compiled step fits: grow while the peak
    # extrapolated from the last two batches stays under the limit
    peaks: dict[int, int] = {}
    compiles = []
    batch = 0
    while True:
        b = batch + 1
        compiled, c_s = compile_step_shapes(cfg, mesh, tcfg, b)
        compiles.append(c_s)
        peaks[b] = compiled.memory_analysis().peak_memory_in_bytes
        log(f"  batch {b}: step compile {c_s:.3f} s, peak {gib(peaks[b])} "
            f"of {gib(limit)}")
        if peaks[b] > limit:
            break
        batch = b
        if b > 1 and 2 * peaks[b] - peaks[b - 1] > limit:
            break
    check(batch >= 1, "not even one sequence fits the chip")
    log(f"global batch {batch} x seq {SEQ}: compiled peak "
        f"{gib(peaks[batch])}, margin {gib(limit - peaks[batch])} under the "
        f"{gib(limit)} limit")

    trainer = make_trainer(cfg, mesh, tcfg, batch, steps, seed)
    _, c_s = compile_trainer_step(trainer)
    compiles.append(c_s)
    log(f"  trainer step compile {c_s:.3f} s")
    log(f"mesh pod/data/model {tuple(mesh.shape.values())}: n_pods=1, so the "
        f"pod exchange is the identity (no pod region in the step)")
    batch0 = {k: jnp.asarray(v) for k, v in trainer.data.batch(0).items()}

    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(lambda p, b: loss_fn(cfg, p, b, jnp.float32))

        def ref_loss(params):
            # per sequence: same mean (equal lengths), a fraction of the memory
            return float(np.mean([
                float(ref_fn(params, {k: v[i:i + 1] for k, v in batch0.items()}))
                for i in range(batch)
            ]))

        t0 = time.perf_counter()
        ref0 = ref_loss(trainer.params)
        log(f"  float32 reference (highest precision) before any update: "
            f"{ref0:.6f} ({time.perf_counter() - t0:.3f} s incl. compile)")

    hist = run_trainer(trainer)
    losses = [h["loss"] for h in hist]
    times = [h["dt"] for h in hist[1:]]
    log(f"losses of steps 1..{steps} (each before its update): {fmt(losses)}")
    log(f"step time (steps 2..{steps}, host clock to block_until_ready): "
        f"median {np.median(times) * 1e3:.3f} ms, min {min(times) * 1e3:.3f} ms")
    rel = abs(losses[0] - ref0) / abs(ref0)
    log(f"step-1 loss {losses[0]:.6f} vs float32 reference {ref0:.6f}: "
        f"relative difference {rel:.3e} (tolerance {LOSS_RTOL_BF16:.3e})")
    check(rel <= LOSS_RTOL_BF16, "first loss disagrees with the f32 reference")
    with jax.default_matmul_precision("highest"):
        ref_n = ref_loss(trainer.params)
    log(f"float32 loss on the first batch after {steps} updates: {ref_n:.6f} "
        f"(before: {ref0:.6f})")
    check(losses[-1] < losses[0], "loss did not fall over the steps")
    check(ref_n < ref0, "loss on the first batch did not fall")
    res_max = max(float(jnp.max(jnp.abs(r)))
                  for r in jax.tree.leaves(trainer.residuals))
    log(f"geococo residuals after {steps} steps: max |r| {res_max} "
        f"(identity exchange leaves them zero)")
    check(res_max == 0.0, "one-pod exchange changed the residuals")
    log(f"trainer: peak_bytes_in_use {gib(peak_bytes(dev))}")
    return compiles


# ---------------------------------------------------------------------------
# --chips 4: the pod exchange
# ---------------------------------------------------------------------------


def phase_pod_exchange(seed: int, steps: int = 3) -> list[float]:
    """Returns the compile seconds of each ahead-of-time compile."""
    import jax
    import jax.numpy as jnp

    from repro.dist.collectives import SyncConfig
    from repro.launch.hlo_cost import collectives_over

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = make_mesh((2, 2, 1), AXES)
    batch = 4  # one sequence per chip
    compiles = []

    def one(n_layers, sync):
        cfg = granite(n_layers)
        trainer = make_trainer(cfg, mesh, train_config(sync), batch, steps, seed)
        compiled, c_s = compile_trainer_step(trainer)
        compiles.append(c_s)
        pod = collectives_over(compiled.as_text(), dict(mesh.shape), "pod")
        counts = {op: pod.count(op) for op in sorted(set(pod))}
        name = sync.strategy + (
            f" density {sync.density}" if sync.needs_residuals else "")
        log(f"{name}: step compile "
            f"{c_s:.3f} s, compiled peak per device "
            f"{gib(compiled.memory_analysis().peak_memory_in_bytes)}, "
            f"pod-axis collectives {counts}")
        check(pod, "no collective over the pod axis in the compiled step")
        hist = run_trainer(trainer)
        log(f"  losses {fmt(h['loss'] for h in hist)}, grad norms "
            f"{fmt(h['grad_norm'] for h in hist)}")
        return trainer, hist

    log(f"mesh pod/data/model {tuple(mesh.shape.values())}, global batch "
        f"{batch} x seq {SEQ}")
    one(8, SyncConfig(strategy="geococo", density=0.1))
    peaks = [peak_bytes(d) for d in devs]
    log(f"peak_bytes_in_use per device: {[gib(p) for p in peaks]}")
    check(max(peaks) <= 1.25 * min(peaks), "device memory is uneven")

    # flat replicates parameters and Adam state on every chip: at 8 layers
    # that is 14 GB before activations, so the comparison runs at 4 layers
    flat = one(4, SyncConfig(strategy="flat"))[1]
    geo, dense = one(4, SyncConfig(strategy="geococo", density=1.0))
    pairs = [(f"loss step {i + 1}", flat[i]["loss"], dense[i]["loss"])
             for i in range(steps)]
    pairs.append(("grad norm step 1", flat[0]["grad_norm"], dense[0]["grad_norm"]))
    for name, a, b in pairs:
        rel = abs(a - b) / abs(a)
        log(f"geococo density 1.0 vs flat, {name}: {b:.6f} vs {a:.6f}, "
            f"relative difference {rel:.3e} (tolerance {FLAT_RTOL:.3e})")
        check(rel <= FLAT_RTOL, f"geococo at density 1.0 differs from flat ({name})")
    res_max = max(float(jnp.max(jnp.abs(r)))
                  for r in jax.tree.leaves(geo.residuals))
    log(f"geococo density 1.0 residuals: max |r| {res_max}")
    check(res_max == 0.0, "density 1.0 left a residual")
    return compiles


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing run")
    log(f"device {dev.device_kind} x {jax.device_count()}, jax "
        f"{jax.__version__}, compile cache {cache}")

    t0 = time.perf_counter()
    if args.chips == 4:
        compiles = phase_pod_exchange(args.seed)
    else:
        compiles = phase_kernels(args.seed) + phase_trainer(args.seed)
    log(f"total compile seconds {sum(compiles):.3f} over {len(compiles)} "
        f"ahead-of-time compiles; wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
    }}), flush=True)


if __name__ == "__main__":
    main()
