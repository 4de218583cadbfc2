"""Readings that the limits of `correct` are set from, for one cell.

    python bench/tools/control.py --workload serve.chat --seeds 11,12,13 --seconds 10

In one process, for each seed: set the cell up, run a short window at the
cell's own load, and put the same sample through the comparison that decides
`correct` twice: for the program, and for the control (the reference one
precision below the configuration's, in the program's place). Prints one
JSON line per seed, each side's `correct` beside its numbers and limits, and
a summary: the largest program reading (the lower end of a limit), the
smallest control reading (its upper end), and whether the program was
correct and the control not correct on every seed. Exits non-zero where
either is not so.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    import jax

    from bench import harness
    from bench.run import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    place_compile_cache(ROOT)
    cell = harness.find_cell(harness.benchmark(ROOT), args.workload)
    lower, upper = {}, {}
    program_ok = control_fails = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        drv = harness.driver(cell.config["kind"]).Driver(cell, seed, args.seconds)
        drv.setup()
        drv.window(harness.Run(cell=args.workload))
        drv.free()
        sides = {"program": drv.check(), "control": drv.control_check()}
        row = {"seed": seed, "failed": drv.failed(), "seconds": time.monotonic() - t}
        for side, chk in sides.items():
            row[side] = {"correct": chk["correct"],
                         "numbers": {n: {"value": v, "limit": lim} for n, v, lim in chk["numbers"]}}
        print(json.dumps(row), flush=True)
        program_ok &= sides["program"]["correct"]
        control_fails &= not sides["control"]["correct"]
        for (n, v, _), (_, c, _) in zip(sides["program"]["numbers"], sides["control"]["numbers"]):
            lower[n] = max(lower.get(n, v), v)
            upper[n] = min(upper.get(n, c), c)
        del drv
    print(json.dumps({"lower": lower, "upper": upper, "program_correct_every_seed": program_ok,
                      "control_not_correct_every_seed": control_fails}), flush=True)
    return 0 if program_ok and control_fails else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
