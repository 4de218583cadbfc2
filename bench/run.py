"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload serve.chat --seed 7 --seconds 30 --trace 0

Loads the cell named in ``BENCHMARK.json``, sets it up (TPU client, weights
or data from the seed, the cell's own shapes warmed), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it profiles a few seconds from the middle of the window and
reports the per-layer metrics. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT
else:
    sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

TRACE_SECONDS = 3.0


class NoChip(RuntimeError):
    pass


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache: JAX_COMPILATION_CACHE_DIR where it is set,
    else the fixed directory ``<checkout>/.jax_cache``. Every program is kept,
    however short its compile, so a second run compiles nothing."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, root: str = ROOT,
             t_start: float = T_START) -> dict:
    """One run of cell `name`; returns the result object (and its notes)."""
    import jax

    from bench import harness
    from bench import trace as tr
    from bench.peaks import peaks_for
    from bench.reference.dense_lm import sizes

    bm = harness.benchmark(root)
    cell = harness.find_cell(bm, name, os.path.join(root, "bench"))
    devices = jax.devices()
    chips = int(cell.entry["chips"])
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"{name} needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    dev = devices[0]
    cache = place_compile_cache(root)
    counter = harness.CompileCounter()
    notes = [f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache}"]

    drv = harness.driver(cell.config["kind"], os.path.join(root, "bench")).Driver(
        cell, seed, seconds)
    drv.setup()
    setup_s = time.monotonic() - t_start

    run = harness.Run(cell=name)
    run.peaks = peaks_for(dev.device_kind) if require_chip else peaks_for("TPU v5 lite")
    if cell.config["kind"] == "serve_lm":
        run.sizes = sizes(drv.conf)
    tracer = None
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        dur = min(TRACE_SECONDS, seconds / 2)
        tracer = harness.Tracer(log_dir, time.monotonic() + (seconds - dur) / 2, dur)
        tracer.start()
    run.counters_before = drv.service.metrics.snapshot()
    counter.active = True
    drv.window(run)
    counter.active = False
    counter.close()
    run.counters_after = drv.service.metrics.snapshot()
    run.tasks = drv.tasks
    if tracer is not None:
        tracer.join(timeout=120)
        if tracer.error is not None:
            raise tracer.error
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    notes.append(f"compiles inside the window: {counter.count} {counter.names[:8]}")
    notes += drv.notes()

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips,
              "memory_peak_bytes": memory_peak}
    metrics = {}
    breakdown = None
    if trace:
        run.trace = tr.load(tr.find_xplane(log_dir), tracer.mark_ns, tracer.t0, tracer.t1)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"], os.path.join(root, "bench"))(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": [list(x) for x in run.trace.top_ops(10)],
                     "idle_gaps": [list(x) for x in run.trace.idle_gaps(10)]}
    e2e = drv.end_to_end()
    notes.append("window: " + json.dumps(e2e))
    if not trace:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}
    notes.append(f"setup_s: {setup_s}")

    drv.free()
    t_check = time.monotonic()
    chk = drv.check()
    notes.append(f"check: {json.dumps(chk.get('checked', {}))} in "
                 f"{time.monotonic() - t_check:.3f} s")
    result = {"correct": chk["correct"], "attempted": drv.attempted(),
              "failed": drv.failed(), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # the compared numbers come last, each beside its limit
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in chk["numbers"]}
    return {"result": result, "notes": notes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        note(f"bench: {exc}")
        return 3
    for line in out["notes"]:
        note(line)
    res = out["result"]
    for n, c in res["checks"].items():
        note(f"check {n}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — reported, then a failing exit code
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # every thread this run started has ended or been told to; leave at once
    # rather than wait on a fabric thread that is still winding down
    os._exit(code)
