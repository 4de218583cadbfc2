"""Find a cell's knee once: one set-up, then one window per offered rate.

    python bench/tools/sweep.py --workload serve.chat --rates 1,2,3,4 --seconds 20

For each rate it prints the window's end-to-end numbers, how many requests
failed or were unfinished when the drain ended, and how long the drain took
(a drain that grows from rate to rate is a growing backlog). The cell's rate
is then fixed in its traffic file at about four fifths of the knee.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4242)
    args = ap.parse_args()

    import jax

    from bench import harness
    from bench.run import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    place_compile_cache(ROOT)
    cell = harness.find_cell(harness.benchmark(ROOT), args.workload)
    drv = harness.driver(cell.config["kind"]).Driver(cell, args.seed, args.seconds)
    t = time.monotonic()
    drv.setup()
    print(f"setup {time.monotonic() - t:.3f} s", flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        drv.plan(mix, seed=args.seed + i)
        run = harness.Run(cell=args.workload)
        drv.window(run)
        row = {"rate_per_s": rate, "attempted": drv.attempted(), "failed": drv.failed(),
               "drain_s": drv.drained_at - drv.window_end, **drv.end_to_end()}
        print(json.dumps(row), flush=True)
        print("  " + " | ".join(drv.notes()), flush=True)
    drv.free()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
