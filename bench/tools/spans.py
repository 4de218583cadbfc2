"""Run one cell with the program's spans on, and read the metrics that need them.

    python bench/tools/spans.py --workload fn.frames --seed 7 --seconds 51 --trace 1

The same run as ``bench/run.py`` (same set-up, window, check and result line),
except that the fabric's registry records spans from the window's start to
its end, and the run reads the span metrics below beside the cell's own.
``bench/run.py`` leaves spans off, so these metrics are not in
``BENCHMARK.json`` yet. With ``--trace 1`` it also prints, on standard error,
the traced slice's device idle time by the span open at each idle instant.
With ``--trace 0`` the end-to-end metrics, read against ``bench/run.py``'s on
the same seed, give the cost of spans when they are on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.run import T_START, NoChip, run_cell  # noqa: E402

# the newest spans of a whole window: about 14 per task at 700 tasks/s
CAPACITY = 1 << 21

# per-layer metrics that read spans: name, unit, the cell that has them
SPAN_METRICS = [
    ("coalesce_ms.decode", "ms", "serve.chat"),
    ("idle_unexplained_pct.chat", "%", "serve.chat"),
    ("h2d_ms.frames", "ms", "fn.frames"),
    ("resolve_ms.frames", "ms", "fn.frames"),
    ("result_ms.frames", "ms", "fn.frames"),
    ("idle_unexplained_pct.frames", "%", "fn.frames"),
    ("client_ms.short", "ms", "fn.short"),
]


def with_spans(harness, runs: list) -> None:
    """Make every driver record spans through its window, and every cell
    report the span metrics; each window's Run is appended to `runs`."""
    find_cell, driver = harness.find_cell, harness.driver

    def find(bm, name, *args):
        cell = find_cell(bm, name, *args)
        have = {m["name"] for m in cell.per_layer}
        cell.per_layer += [{"name": n, "unit": u} for n, u, c in SPAN_METRICS
                           if c == name and n not in have]
        return cell

    def spanned(kind, *args):
        base = driver(kind, *args).Driver

        class Driver(base):
            def window(self, run):
                self.service.metrics.enable_spans(CAPACITY)
                try:
                    super().window(run)
                finally:
                    run.spans = self.service.metrics.take_spans()
                    runs.append(run)

        return type("SpannedDriver", (), {"Driver": Driver})

    harness.find_cell, harness.driver = find, spanned


def span_notes(run) -> list:
    from bench.harness import median
    from bench.spans import idle_attribution

    t0, t1 = (int(t * 1e9) for t in run.window)
    by_name: dict = {}
    for s in run.spans:
        if t0 <= s.start_ns <= t1:
            by_name.setdefault(s.name, []).append((s.end_ns - s.start_ns) * 1e-6)
    notes = ["spans in the window (name: count, median ms, total s): " + "; ".join(
        f"{n}: {len(v)}, {median(v):.4f}, {sum(v) * 1e-3:.4f}"
        for n, v in sorted(by_name.items()))]
    idle = idle_attribution(run)
    if idle is not None:
        notes.append("idle seconds by span: " + "; ".join(
            f"{n or 'no span open'}: {v:.6f}"
            for n, v in sorted(idle.items(), key=lambda kv: -kv[1])))
    return notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    runs: list = []
    with_spans(harness, runs)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START)
    except NoChip as exc:
        print(f"spans: {exc}", file=sys.stderr)
        return 3
    for line in out["notes"] + span_notes(runs[-1]):
        print(line, file=sys.stderr)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
