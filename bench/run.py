#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout.  With ``--trace 0`` the result holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a profiler trace of
the first steps of the window.  The last line of standard output is the
result, one JSON object; the numbers the check compared, each beside its
limit, are the last lines of standard error.  A run that finds no TPU, or
fewer chips than the cell needs, prints no result and exits non-zero.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    cell = harness.resolve(args.workload)
    harness.enable_compile_cache()
    try:
        device = harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, device, log=print)
    print(f"run: {time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
