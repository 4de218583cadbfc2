"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only latency,scaling,...]

Emits ``name,us_per_call,derived`` CSV rows (also captured in
benchmarks/results/bench.json).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.launch.compile_cache import enable_compile_cache

SUITES = (
    "latency",        # Fig. 4/5, Table 2
    "scaling",        # Fig. 6 strong + weak
    "throughput",     # §6.2.3
    "federation",     # multi-endpoint fabric: policies x endpoint counts
    "heterogeneity",  # §5.3-5.4/§8: typed container pools + capability routing
    "elasticity",     # §5.4 managed elasticity: blocks-over-time under burst
    "workflow",       # §7 pipelines: diamond DAG vs. linear Flow
    "fault",          # Fig. 7
    "chaos",          # durability tier: faults + full fabric restart, exactly-once
    "datafabric",     # data tier: DataRef vs inline, eta_aware routing, speculation
    "million",        # scale tier: sharded fair-mode forwarder + tenant fairness
    "serving",        # serving tier: KV-affinity routing + continuous batching
    "memoization",    # Table 3
    "warming",        # Table 4 (container instantiation analogue)
    "batching",       # Fig. 8
    "prefetch",       # Fig. 9
    "roofline",       # deliverable (g), from the dry-run artifacts
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", help="comma-separated subset of suites")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny parameters for CI smoke runs")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    selected = args.only.split(",") if args.only else list(SUITES)

    print("name,us_per_call,derived")
    all_rows = []
    t_start = time.monotonic()
    for suite in selected:
        mod = __import__(f"benchmarks.bench_{suite}", fromlist=["run"])
        t0 = time.monotonic()
        rows = mod.run()
        all_rows.extend(rows)
        print(f"# suite {suite}: {len(rows)} rows in {time.monotonic()-t0:.1f}s",
              flush=True)
    print(f"# total: {len(all_rows)} rows in {time.monotonic()-t_start:.1f}s")

    out = os.path.join(os.path.dirname(__file__), "results", "bench.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1)


if __name__ == "__main__":
    main()
