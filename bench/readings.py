#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python bench/readings.py --workload <name> --seeds 11,12,... \\
        --control-seeds 11,12,13 [--out chiprun_out/readings.jsonl]

For each seed the program runs the cell's checked steps through
``Trainer.run`` exactly as a benchmark run does (no measured window), and
the float32 reference follows the same steps.  For each control seed the
reference also runs in the program's place twice more: as the control
(``mode="fp8"``) and with half of each batch left out, the mean taken over
the rest.  Each comparison prints one JSON line: the numbers the check
compares and, for each leaf number, the leaf that set it.

The benchmark's own runs never run this; it is how the lower and upper
readings in ``bench/limits/<workload>.json`` were taken.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def worst(ours: dict, ref: dict) -> str:
    import numpy as np

    med = float(np.median(list(ref.values())))
    return max(ref, key=lambda k: abs(ours[k] - ref[k]) / max(ref[k], med))


def readings(cell, seeds, control_seeds, emit) -> None:
    from bench.kinds import train

    batch = cell.program["global_batch"]
    f32, fp8 = train.reference(cell), train.reference(cell, mode="fp8")
    half = train.reference(cell, rows=batch // 2)

    def record(seed, kind, side, ref, seconds):
        emit({"seed": seed, "kind": kind, **train.numbers(side, ref),
              "grad_leaf": worst(side["grad_norms"], ref["grad_norms"]),
              "update_leaf": worst(side["change_norms"], ref["change_norms"]),
              "losses": side["losses"], "ref_losses": ref["losses"],
              "seconds": seconds})

    for seed in seeds:
        t0 = time.perf_counter()
        trainer = train.build_trainer(cell, seed)
        spans = train.instrument(trainer)
        state = train.set_up_steps(trainer)
        b1 = trainer.tcfg.optim.b1
        consumed = spans.batches
        del trainer, spans
        gc.collect()
        side = train.program_side(state, b1)
        t1 = time.perf_counter()
        ref = f32.train(seed, consumed)
        record(seed, "program", side, ref, [t1 - t0, time.perf_counter() - t1])
        if seed in control_seeds:
            t1 = time.perf_counter()
            record(seed, "control_fp8", fp8.train(seed, consumed), ref,
                   time.perf_counter() - t1)
            t1 = time.perf_counter()
            record(seed, "fault_half_batch", half.train(seed, consumed, rows=batch // 2),
                   ref, time.perf_counter() - t1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import harness

    cell = harness.resolve(args.workload)
    harness.enable_compile_cache()
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        readings(cell, seeds, control, emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
