"""Arithmetic over the program's own spans and counters, for the per-layer
metrics that read them. A run carries the spans in ``run.spans`` (the
registry's ``take_spans()``: name, task, parent, thread, start_ns, end_ns on
the host's monotonic clock) where its window recorded them; every function
here returns None where the run holds nothing to read."""
from __future__ import annotations

import heapq
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.harness import Run, median


def _spans(run: Run) -> list:
    return getattr(run, "spans", None) or []


def _ms(span) -> float:
    return (span.end_ns - span.start_ns) * 1e-6


def per_task_ms(run: Run, kind: str, names: Iterable[str]) -> Optional[float]:
    """Median, over the window's tasks of `kind` that recorded any span in
    `names`, of each task's summed time in those spans, ms."""
    names = set(names)
    ids = {r.future.task_id for r in run.tasks_of(kind)}
    total: Dict[str, float] = {}
    for s in _spans(run):
        if s.name in names and s.task in ids:
            total[s.task] = total.get(s.task, 0.0) + _ms(s)
    return median(list(total.values())) if total else None


def span_median_ms(run: Run, name: str) -> Optional[float]:
    """Median length of the spans named `name` that started in the window, ms."""
    t0, t1 = (int(t * 1e9) for t in run.window)
    vals = [_ms(s) for s in _spans(run) if s.name == name and t0 <= s.start_ns <= t1]
    return median(vals) if vals else None


def counter_delta(run: Run, name: str) -> Optional[int]:
    """How far counter `name` moved over the window; None where the program
    has no such counter."""
    after = run.counters_after.get("counters", {})
    if name not in after:
        return None
    return after[name] - run.counters_before.get("counters", {}).get(name, 0)


def idle_gaps(busy: Sequence[Tuple[float, float]], t0: float,
              t1: float) -> List[Tuple[float, float]]:
    """The complement of sorted, disjoint `busy` intervals inside [t0, t1]."""
    edges = [(t0, t0)] + list(busy) + [(t1, t1)]
    return [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]


def idle_by_span(gaps: Sequence[Tuple[float, float]],
                 spans: Sequence[Tuple[float, float, str]]) -> Dict[Optional[str], float]:
    """Seconds of `gaps` put down to a span name: at each instant, the open
    span (start, end, name) that started last, on any thread, which is
    always one with no open child; None where no span was open."""
    order = sorted(spans)
    out: Dict[Optional[str], float] = {}
    open_: list = []          # (-start, end, name) of every span begun so far
    j = 0
    for a, b in sorted(gaps):
        t = a
        while t < b:
            while j < len(order) and order[j][0] <= t:
                s, e, n = order[j]
                heapq.heappush(open_, (-s, e, n))
                j += 1
            while open_ and open_[0][1] <= t:
                heapq.heappop(open_)
            nxt = b
            if j < len(order):
                nxt = min(nxt, order[j][0])
            name = None
            if open_:
                name = open_[0][2]
                nxt = min(nxt, open_[0][1])
            out[name] = out.get(name, 0.0) + (nxt - t)
            t = nxt
    return out


def idle_attribution(run: Run) -> Optional[Dict[Optional[str], float]]:
    """Idle seconds of the traced slice by span name (None: no span open);
    None where the trace saw no device operation at all."""
    if run.trace is None or not run.trace.ops or not _spans(run):
        return None
    tr = run.trace
    gaps = idle_gaps(tr.busy_intervals(), tr.t0, tr.t1)
    return idle_by_span(gaps, [(s.start_ns * 1e-9, s.end_ns * 1e-9, s.name)
                               for s in _spans(run)])


def idle_unexplained_pct(run: Run) -> Optional[float]:
    """Share of the traced slice's idle time in which no span was open, %."""
    by = idle_attribution(run)
    idle = sum(by.values()) if by else 0.0
    if idle <= 0:
        return None
    return 100.0 * by.get(None, 0.0) / idle
