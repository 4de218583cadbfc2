"""Arithmetic the per-layer metric files share. Each returns None where the
run holds nothing to read, never 0 for a share of a peak."""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from bench import flops
from bench.harness import Run, median
from bench.trace import Event

DECODE = "jit_decode_step"


def fabric_ms(run: Run, kind: str) -> Optional[float]:
    """Median, over the window's tasks of `kind`, of the task's time outside
    its function (total minus t_e), in milliseconds."""
    vals = [(b["total"] - b["t_e"]) * 1e3
            for b in (r.breakdown() for r in run.tasks_of(kind))]
    return median(vals) if vals else None


def exec_ms(run: Run, kind: str) -> Optional[float]:
    vals = [r.breakdown()["t_e"] * 1e3 for r in run.tasks_of(kind)]
    return median(vals) if vals else None


def device_idle_pct(run: Run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share()


def histogram_mean(run: Run, name: str) -> Optional[float]:
    d = run.histogram_delta(name)
    if not d or d["count"] <= 0:
        return None
    return d["sum"] / d["count"]


def _inside(run: Run, name: str) -> List[Event]:
    tr = run.trace
    return [m for m in tr.modules
            if m.name == name and m.start >= tr.t0 and m.start + m.dur <= tr.t1]


def _kernels_during(run: Run, module: Event, name: str):
    end = module.start + module.dur
    return [o for o in run.trace.ops
            if o.kernel and o.module == name and module.start <= o.start <= end]


def decode_steps(run: Run) -> List[Tuple[Event, List[int]]]:
    """Decode programs wholly inside the traced slice, each with the KV
    lengths of the tokens it served: a task belongs to the last decode
    program that ended before the task did."""
    if run.trace is None:
        return []
    tr = run.trace
    every = sorted((m for m in tr.modules if m.name == DECODE), key=lambda m: m.start)
    ends = [m.start + m.dur for m in every]
    inside = {id(m) for m in _inside(run, DECODE)}
    served: Dict[int, List[int]] = {}
    for r in run.tasks:
        if r.kind != "decode" or not r.ok() or not (tr.t0 <= r.ts.exec_end <= tr.t1):
            continue
        i = bisect.bisect_right(ends, r.ts.exec_end) - 1
        if i >= 0 and id(every[i]) in inside:
            served.setdefault(i, []).append(int(r.meta["n_tokens"]))
    return [(every[i], kv) for i, kv in sorted(served.items())]


def step_mfu_decode(run: Run) -> Optional[float]:
    steps = decode_steps(run)
    if not steps or run.sizes is None:
        return None
    work = sum(flops.decode_token_flops(run.sizes, n) for _, kv in steps for n in kv)
    t = sum(m.dur for m, _ in steps)
    return 100.0 * work / (t * run.peaks["bf16_flops"]) if t > 0 else None


def decode_attention_roofline(run: Run) -> Optional[float]:
    steps = decode_steps(run)
    if not steps or run.sizes is None:
        return None
    least = spent = 0.0
    for m, kv in steps:
        ks = _kernels_during(run, m, DECODE)
        if not ks:
            continue
        least += flops.roofline_time(flops.decode_attention_kernel(run.sizes, kv),
                                     run.peaks)["seconds"]
        spent += sum(k.dur for k in ks)
    return 100.0 * least / spent if spent > 0 else None

