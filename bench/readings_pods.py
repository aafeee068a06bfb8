#!/usr/bin/env python3
"""Readings that a pod cell's limits are set from, many seeds in one process.

    python bench/readings_pods.py --workload <name> [--seeds 11,12,...] \\
        [--control-seeds 13] [--out chiprun_out/readings_pods.jsonl]

For each seed the program runs the cell's checked steps through
``Trainer.run`` exactly as a benchmark run does (no measured window), and
the float32 reference follows the same steps.  For each control seed the
reference runs, on the batches the generator gives for it, in the
program's place and against itself in float32: as the control
(``mode="fp8"``); with each fault of the exchange (the pod mean taken
before the filter, the exchange left out, the residual dropped); and with
half of each pod's rows left out (capacity still reckoned over the whole
share).  Each comparison prints one JSON line:
the numbers the check compares and, for each leaf number, the leaf that
set it.

The benchmark's own runs never run this; it is how the lower and upper
readings in ``bench/limits/<workload>.json`` of a pod cell were taken.
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


def readings(cell, seeds, control_seeds, emit) -> None:
    from bench.kinds import train, train_pods
    from bench.readings import worst

    share = cell.program["global_batch"] // train_pods.pods(cell)
    checked = cell.traffic["checked_steps"]
    f32 = train_pods.reference(cell)

    def record(seed, kind, side, ref, seconds):
        emit({"seed": seed, "kind": kind, **train_pods.numbers(side, ref),
              "grad_leaf": worst(side["grad_norms"], ref["grad_norms"]),
              "update_leaf": worst(side["change_norms"], ref["change_norms"]),
              "residual_leaf": [worst(a, b) for a, b in
                                zip(side["residual_norms"], ref["residual_norms"])],
              "losses": side["losses"], "ref_losses": ref["losses"],
              "seconds": seconds})

    for seed in seeds:
        t0 = time.perf_counter()
        trainer, spans, state, steps = train_pods.set_up(cell, seed)
        b1 = trainer.tcfg.optim.b1
        consumed = spans.batches
        del trainer, spans, steps
        gc.collect()
        side = train_pods.program_side(state, b1)
        t1 = time.perf_counter()
        ref = f32.train(seed, consumed)
        record(seed, "program", side, ref, [t1 - t0, time.perf_counter() - t1])

    # the reference in the program's place, on the batches the program
    # would consume (the benchmark's own copy of the generator)
    fp8 = train_pods.reference(cell, mode="fp8") if control_seeds else None
    for seed in control_seeds:
        batches = train.regenerate(cell, seed, list(range(checked)))
        t1 = time.perf_counter()
        ref = f32.train(seed, batches)
        emit({"seed": seed, "kind": "reference", "seconds": time.perf_counter() - t1})
        for kind, reference, how in [
                ("control_fp8", fp8, {}),
                ("fault_mean_first", f32, {"fault": "mean_first"}),
                ("fault_no_exchange", f32, {"fault": "no_exchange"}),
                ("fault_no_residual", f32, {"fault": "no_residual"}),
                ("fault_half_batch", f32, {"rows": share // 2})]:
            t1 = time.perf_counter()
            record(seed, kind, reference.train(seed, batches, **how), ref,
                   time.perf_counter() - t1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
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
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    from bench.kinds import train_pods

    try:
        with train_pods.keyed_with_metadata():
            readings(cell, seeds, control, emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
