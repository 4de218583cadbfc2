"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

What a TPU trace holds, as read by hand from one (PERF.md, section 3): the
plane ``/device:TPU:<n>`` has a line ``XLA Modules`` with one event per
program run, named ``jit_<function>(<fingerprint>)``, and a line ``XLA Ops``
with one event per HLO operation, named by the instruction's text
(``%name = shape op(operands...)``); a Pallas kernel is a ``custom-call``
there, whose text names ``custom_call_target="tpu_custom_call"``. Host planes hold TraceMe events. Every event's ``start_ns`` counts
from one origin for all planes, so one host annotation at a known
``time.monotonic_ns()`` puts device events on the host's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

MARK = "bench_clock_mark"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_SHAPE = re.compile(r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")


@dataclass
class Event:
    name: str
    start: float     # seconds on the host's monotonic clock
    dur: float       # seconds


@dataclass
class Op(Event):
    module: str = ""     # program the op ran in, e.g. "jit_decode_step"
    kind: str = ""       # instruction name without its number, e.g. "fusion"
    kernel: bool = False  # a tpu_custom_call: a Pallas kernel
    dtype: str = ""
    shape: Tuple[int, ...] = ()


@dataclass
class Trace:
    """One device's view of a traced window, on the host's monotonic clock."""

    t0: float
    t1: float
    modules: List[Event] = field(default_factory=list)
    ops: List[Op] = field(default_factory=list)
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy_intervals(self) -> List[Tuple[float, float]]:
        """Union of the intervals in which an operation ran, inside the window."""
        spans = sorted((max(o.start, self.t0), min(o.start + o.dur, self.t1))
                       for o in self.ops)
        out: List[List[float]] = []
        for a, b in spans:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy_intervals())

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernels_in(self, module: str) -> List[Op]:
        return [o for o in self.ops if o.kernel and o.module == module]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device time by stable op name (``module/kind``), loops left out
        because their bodies' ops are counted themselves."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            if o.kind in ("while", "conditional", "call"):
                continue
            key = f"{o.module}/{'kernel:' + o.kind if o.kernel else o.kind}"
            tot[key] = tot.get(key, 0.0) + o.dur
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """Longest idle gaps, each named by the programs around it and by the
        host event that overlapped it most."""
        busy = self.busy_intervals()
        gaps = []
        edges = [(self.t0, self.t0)] + busy + [(self.t1, self.t1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append((a, b))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            before = _module_at(self.modules, a, before=True)
            after = _module_at(self.modules, b, before=False)
            host = _host_in(self.host, a, b)
            out.append((f"{before} -> {after} | host: {host}", b - a))
        return out


def _module_at(modules: List[Event], t: float, before: bool) -> str:
    best, best_d = "window edge", None
    for m in modules:
        d = t - (m.start + m.dur) if before else m.start - t
        if d >= -1e-6 and (best_d is None or d < best_d):
            best, best_d = m.name, d
    return best


def _host_in(host: List[Event], a: float, b: float) -> str:
    best, best_ov = "nothing traced", 0.0
    for e in host:
        ov = min(b, e.start + e.dur) - max(a, e.start)
        if ov > best_ov:
            best, best_ov = e.name, ov
    return best


def _parse_op(text: str) -> Tuple[str, bool, str, Tuple[int, ...]]:
    m = _OP_NAME.match(text)
    kind = m.group(1) if m else text.split()[0].lstrip("%")
    kernel = 'custom_call_target="tpu_custom_call"' in text
    s = _SHAPE.search(text)
    dtype, shape = "", ()
    if s:
        dtype = s.group(1)
        shape = tuple(int(x) for x in s.group(2).split(",") if x)
    return kind, kernel, dtype, shape


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


# host TraceMe events worth naming a gap by: the runtime's own work
_HOST_SKIP = ("ReadSyncFlag", "Release semaphore", "MemoryDeallocation")


def load(path: str, mark_mono_ns: int, t0: float, t1: float,
         device: str = "/device:TPU:0", mark: str = MARK) -> Trace:
    """Read `path` and return device `device`'s events between t0 and t1
    (host monotonic seconds). `mark_mono_ns` is the monotonic time taken
    inside the trace annotation named `mark`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    offset = None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == mark:
                    offset = mark_mono_ns - e.start_ns
                    break
    if offset is None:
        raise ValueError(f"{path}: no {mark} annotation in the host planes")

    def sec(ns: float) -> float:
        return (ns + offset) * 1e-9

    tr = Trace(t0=t0, t1=t1)
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        tr.modules.append(Event(_FINGERPRINT.sub("", e.name),
                                                sec(e.start_ns), e.duration_ns * 1e-9))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        kind, kernel, dtype, shape = _parse_op(e.name)
                        tr.ops.append(Op(e.name[:160], sec(e.start_ns), e.duration_ns * 1e-9,
                                         kind=kind, kernel=kernel, dtype=dtype, shape=shape))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and e.name not in _HOST_SKIP and e.name != mark:
                        tr.host.append(Event(e.name, sec(e.start_ns), e.duration_ns * 1e-9))
    tr.modules.sort(key=lambda m: m.start)
    _assign_modules(tr)
    tr.modules = [m for m in tr.modules if m.start + m.dur > t0 and m.start < t1]
    tr.ops = [o for o in tr.ops if o.start + o.dur > t0 and o.start < t1]
    return tr


def _assign_modules(tr: Trace) -> None:
    """Give each op the program whose run contains its start."""
    mods = tr.modules
    starts = [m.start for m in mods]
    for o in tr.ops:
        i = bisect.bisect_right(starts, o.start + 1e-9) - 1
        if i >= 0 and o.start <= mods[i].start + mods[i].dur + 1e-9:
            o.module = mods[i].name
