"""The benchmark's shared machinery: lookup by name, records, timing, tracing.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found here by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: one configuration; its ``kind`` names the
  driver ``bench/drivers/<kind>.py`` that deploys it;
- ``bench/traffic/<traffic>.json``: one traffic mix, read by ``traffic.py``;
- ``bench/metrics/<metric>.py``: one per-layer metric, a ``read(run)`` that
  returns a number or None when the run holds nothing to read;
- ``bench/functions/<function>.py``: one function that a task mix sends.
"""
from __future__ import annotations

import importlib.util
import json
import os
import statistics
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


# ---------------------------------------------------------------- lookup
def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: Dict                 # the workload's entry in BENCHMARK.json
    config: Dict                # the configuration file's content
    traffic: Dict               # the traffic mix's parameters
    end_to_end: List[Dict]      # end-to-end metric entries this cell reports
    per_layer: List[Dict]       # per-layer metric entries this cell reports


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bm: Dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {[w['name'] for w in bm['workloads']]}")
    conf_entry = next(c for c in bm["configs"] if c["name"] == entry["config"])
    root = os.path.dirname(bench_dir)
    config = load_json(os.path.join(root, conf_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
    return Cell(
        name=name, entry=entry, config=config, traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bm["per_layer"] if _reports(m, name)],
    )


def driver(kind: str, bench_dir: str = BENCH_DIR):
    return _module(os.path.join(bench_dir, "drivers", kind + ".py"), f"bench_driver_{kind}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    mod = _module(os.path.join(bench_dir, "metrics", name + ".py"),
                  "bench_metric_" + name.replace(".", "_"))
    return mod.read


def function(name: str, bench_dir: str = BENCH_DIR):
    return _module(os.path.join(bench_dir, "functions", name + ".py"), f"bench_fn_{name}")


# ---------------------------------------------------------------- records
@dataclass
class TaskRec:
    """One fabric task this benchmark submitted, read from its future."""

    kind: str                       # which function: "prefill", "decode", "task", ...
    future: Any
    meta: Dict = field(default_factory=dict)

    @property
    def ts(self):
        return self.future.timestamps

    def ok(self) -> bool:
        return self.future.done() and self.future.exception(0) is None

    def breakdown(self) -> Dict[str, float]:
        return self.ts.breakdown()


def record_futures(service, kinds: Dict[str, str], meta_of: Callable, sink: List[TaskRec]):
    """Keep every future that calls of this instance's ``run`` create, with
    the function's kind; the program is not changed."""
    orig = service.run
    lock = threading.Lock()

    def run(function_id, payload, *args, **kwargs):
        fut = orig(function_id, payload, *args, **kwargs)
        kind = kinds.get(function_id, function_id)
        rec = TaskRec(kind, fut, meta_of(kind, payload))
        with lock:
            sink.append(rec)
        return fut

    service.run = run
    return orig


@dataclass
class Run:
    """What one run measured; the per-layer readers take this."""

    cell: str
    window: tuple = (0.0, 0.0)            # host monotonic seconds
    tasks: List[TaskRec] = field(default_factory=list)
    counters_before: Dict = field(default_factory=dict)
    counters_after: Dict = field(default_factory=dict)
    trace: Any = None                     # trace.Trace of the traced slice
    sizes: Optional[Dict[str, int]] = None
    peaks: Optional[Dict[str, float]] = None

    def tasks_of(self, kind: str) -> List[TaskRec]:
        """Tasks of `kind` that succeeded and finished inside the window."""
        t0, t1 = self.window
        return [r for r in self.tasks
                if r.kind == kind and r.ok() and t0 <= r.ts.result_ready <= t1]

    def histogram_delta(self, name: str) -> Optional[Dict[str, float]]:
        a = self.counters_before.get("histograms", {}).get(name, {})
        b = self.counters_after.get("histograms", {}).get(name, {})
        if not b:
            return None
        n = b.get("count", 0) - a.get("count", 0)
        s = b.get("sum", 0.0) - a.get("sum", 0.0)
        return {"count": n, "sum": s}


# ---------------------------------------------------------------- statistics
def percentile(values: List[float], q: float) -> float:
    """The q-th percentile of the raw values, linear between order stats."""
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))


def median(values: List[float]) -> float:
    return float(statistics.median(values))


# ---------------------------------------------------------------- compiles
class CompileCounter:
    """Counts programs lowered (compiled, or loaded from the persistent cache)
    while `active` is set: the measured window should see none."""

    _EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

    def __init__(self):
        import jax.monitoring as mon

        self.active = False
        self.count = 0
        self.names: List[str] = []

        def listener(event, duration, **kw):
            if self.active and event in self._EVENTS:
                self.count += 1
                self.names.append(str(kw.get("fun_name", "")))

        self._listener = listener
        mon.register_event_duration_secs_listener(listener)

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._listener)


# ---------------------------------------------------------------- tracing
def trace_offset(requests: Optional[List[Any]], seconds: float, dur: float) -> float:
    """Where the traced slice starts, in seconds after the window opens.

    With a schedule (`requests`, each with its `due` time): the first due
    time at or after the middle, ``(seconds - dur) / 2``, among those that
    leave room for the whole slice before `seconds`; failing that the last
    that does. A closed loop (`requests` None), or a schedule with no room,
    takes the middle. Only the schedule decides, so a program of any speed
    is traced over the same seconds, and the slice opens as a request
    arrives: its prefill and first decode steps lie inside it.
    """
    mid = (seconds - dur) / 2
    fits = sorted(r.due for r in requests or () if r.due + dur <= seconds)
    if not fits:
        return mid
    return next((d for d in fits if d >= mid), fits[-1])


class Tracer(threading.Thread):
    """Profiles `duration` seconds, from a thread of its own into `log_dir`,
    starting `offset` seconds after `run`'s window opens."""

    def __init__(self, log_dir: str, run: Run, offset: float, duration: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.log_dir, self.timed, self.offset, self.duration = log_dir, run, offset, duration
        self.mark_ns = 0
        self.t0 = self.t1 = 0.0
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        import jax

        from .trace import MARK

        try:
            # the driver sets run.window as its window opens
            while not self.timed.window[0]:
                time.sleep(0.001)
            time.sleep(max(0.0, self.timed.window[0] + self.offset - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(MARK):
                self.mark_ns = time.monotonic_ns()
            self.t0 = time.monotonic()
            time.sleep(self.duration)
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()
        except BaseException as exc:  # noqa: BLE001 — reported by the caller
            self.error = exc
