"""Driver for the function path (``kind: fn_endpoint``).

Deployment: one ``FunctionService`` with an in-memory object store (payload
and result leaves over the spill threshold travel as ``DataRef``s), one
endpoint of the configured executors and workers, and the mix's function
registered with ``jax_jit=True``. The window drives ``FunctionService.run``:
an open loop of arrivals, or a closed loop that keeps a number of tasks in
flight. One collector thread takes each finished future, fetches any spilled
result leaves and stamps when the answer was in hand.
"""
from __future__ import annotations

import gc
import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from bench import harness
from bench import traffic as gen
from bench.harness import Run, TaskRec, percentile, record_futures
from repro.core.datastore import scan_refs

_DONE = object()


@dataclass
class TaskOut:
    idx: int
    due: float
    received: float = 0.0
    error: Optional[str] = None
    result: Any = None


class Driver:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell = cell
        self.conf = cell.config
        self.mix = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.fn = harness.function(self.mix["function"])
        self.tasks: List[TaskRec] = []
        self.plan(self.mix)

    def plan(self, mix: Dict, seed: Optional[int] = None) -> None:
        """The tasks of the next window (a sweep re-plans at other rates)."""
        seed = self.seed if seed is None else seed
        self.requests = gen.tasks(mix, seed, self.seconds)
        self.outs: Dict[int, TaskOut] = {}
        self.kept_payloads: Dict[int, Any] = {}
        self.lateness: List[float] = []
        k = int(mix["check"]["tasks"])
        if self.requests is not None:
            self.keep = set(gen.sample_indices(seed, [r.idx for r in self.requests], k))
        else:
            self.keep = None          # closed loop: kept by a seeded stride
            self.stride = int(mix["check"]["stride"])
            self.offset = int(gen.rng_for(seed, 6).integers(self.stride))

    def _kept(self, idx: int) -> bool:
        if self.keep is not None:
            return idx in self.keep
        return idx % self.stride == self.offset

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import FunctionService
        from repro.core.datastore import InMemoryStore

        conf = self.conf
        self.service = FunctionService(datastore=InMemoryStore(),
                                       spill_threshold=int(conf["spill_threshold_bytes"]))
        ep = conf["endpoint"]
        self.endpoint = self.service.make_endpoint(
            "chip0", n_executors=ep["n_executors"], workers_per_executor=ep["workers_per_executor"])
        self.fid = self.service.register_function(
            self.fn.device_fn, name=self.mix["function"], jax_jit=bool(conf["jax_jit"]))
        self.shared = self.fn.shared(self.seed, self.mix["payload"])
        refs = {k: self.service.put_data(v) for k, v in self.shared.items()}
        self.make = self.fn.Payloads(self.seed, self.mix["payload"], refs)
        record_futures(self.service, {self.fid: "task"}, lambda kind, doc: {}, self.tasks)
        workers = ep["n_executors"] * ep["workers_per_executor"]
        warm = [self.service.run(self.fid, self.make(-1 - i)) for i in range(2 * workers)]
        for f in warm:
            self.service.fetch(f, timeout=1200)

    # ------------------------------------------------------------ window
    def _submit(self, idx: int, due: float, q: "queue.Queue") -> bool:
        """Send task `idx`; a submission the fabric refuses (no live
        endpoint) is a failed task with no answer to check."""
        doc = self.make(idx)
        out = self.outs[idx] = TaskOut(idx, due)
        try:
            fut = self.service.run(self.fid, doc)
        except Exception as exc:  # noqa: BLE001 — counted as failed
            out.received = time.monotonic()
            out.error = f"{type(exc).__name__}: {exc}"
            return False
        if self._kept(idx):
            self.kept_payloads[idx] = doc
        fut.add_done_callback(lambda f, i=idx: q.put((i, f)))
        return True

    def _collect(self, q: "queue.Queue", on_done=None) -> None:
        while True:
            item = q.get()
            if item is _DONE:
                return
            idx, fut = item
            out = self.outs[idx]
            try:
                raw = fut.result(0)
                value = self.service.fetch(raw)
                out.received = time.monotonic()
                if self._kept(idx):
                    out.result = value
                # the client has its answer: drop spilled result blobs, which
                # the store would otherwise keep for the whole run
                for ref in scan_refs(raw):
                    self.service.datastore.delete(ref.key)
            except Exception as exc:  # noqa: BLE001 — counted as failed
                out.received = time.monotonic()
                out.error = f"{type(exc).__name__}: {exc}"
            if on_done is not None:
                on_done(idx)

    def window(self, run: Run) -> None:
        drain = float(self.mix.get("drain_s", 60))
        q: "queue.Queue" = queue.Queue()
        t0 = time.monotonic()
        t1 = t0 + self.seconds
        run.window = (t0, t1)
        self.window_end = t1
        if self.requests is not None:          # open loop
            collector = threading.Thread(target=self._collect, args=(q,), daemon=True)
            collector.start()
            for r in self.requests:
                due = t0 + r.due
                time.sleep(max(0.0, due - time.monotonic()))
                self.lateness.append(time.monotonic() - due)
                self._submit(r.idx, due, q)
        else:                                   # closed loop
            nxt = [0]
            lock = threading.Lock()

            def refill(_idx):
                # keep one task in flight in this place of the loop; a
                # refused submission is retried after a short back-off
                while True:
                    with lock:
                        if time.monotonic() >= t1:
                            return
                        i = nxt[0]
                        nxt[0] += 1
                    if self._submit(i, time.monotonic(), q):
                        return
                    time.sleep(0.01)

            collector = threading.Thread(target=self._collect, args=(q, refill), daemon=True)
            collector.start()
            for _ in range(int(self.mix["in_flight"])):
                refill(None)
        time.sleep(max(0.0, t1 - time.monotonic()))
        deadline = t1 + drain
        while time.monotonic() < deadline and any(
                o.received == 0.0 for o in list(self.outs.values())):
            time.sleep(0.01)
        q.put(_DONE)
        collector.join(timeout=max(0.0, deadline + 5 - time.monotonic()))
        self.drained_at = time.monotonic()

    # ------------------------------------------------------------ results
    def _due_in_window(self) -> List[TaskOut]:
        return [o for o in self.outs.values() if o.due < self.window_end]

    def attempted(self) -> int:
        return len(self._due_in_window())

    def failed(self) -> int:
        return sum(o.received == 0.0 or o.error is not None for o in self._due_in_window())

    def end_to_end(self) -> Dict[str, float]:
        outs = [o for o in self._due_in_window() if o.received and o.error is None]
        lat = [o.received - o.due for o in outs]
        res = {}
        if self.requests is not None and lat:
            res["task_p95_s"] = percentile(lat, 95)
            res["task_p50_s"] = percentile(lat, 50)
        if self.requests is None:
            in_window = [o for o in outs if o.received <= self.window_end]
            res["tasks_per_s"] = len(in_window) / self.seconds
            if lat:
                res["task_p50_s"] = percentile(lat, 50)
        res["tasks_done"] = len(outs)
        return res

    def free(self) -> None:
        self.service.shutdown()
        gc.collect()

    def _compare(self, answer_of) -> Dict:
        """Every kept task's answer, as `answer_of` gives it, against the numpy
        reference of its own payload; a kept task with no answer fails."""
        worst: Dict[str, float] = {name: 0.0 for name in self.fn.LIMITS}
        n = missing = 0
        for idx, doc in self.kept_payloads.items():
            if self.outs[idx].due >= self.window_end:
                continue
            full = self.fn.materialize(doc, self.shared)
            got = answer_of(idx, full)
            if got is None:
                missing += 1
                continue
            for name, v in self.fn.compare(got, self.fn.reference(full)).items():
                worst[name] = max(worst[name], float(v))
            n += 1
        numbers = [(name, worst[name], lim) for name, lim in self.fn.LIMITS.items()]
        numbers.append(("kept_tasks_unanswered", missing, 0))
        ok = n > 0 and missing == 0 and all(v <= lim for _, v, lim in numbers)
        return {"correct": bool(ok), "numbers": numbers, "checked": {"tasks": n}}

    def check(self) -> Dict:
        """The answers the program returned in the window."""
        return self._compare(lambda idx, full: self.outs[idx].result)

    def control_check(self) -> Dict:
        """The same comparison with the control in the program's place: the
        function one precision below the stated one, run on the device over
        the same kept payloads."""
        import jax

        ctl = jax.jit(self.fn.control_fn)
        return self._compare(lambda idx, full: jax.tree.map(np.asarray, ctl(full)))

    def notes(self) -> List[str]:
        late = self.lateness or [0.0]
        return [f"generator lateness: median {np.median(late):.6f} s, "
                f"max {np.max(late):.6f} s over {len(self.lateness)} arrivals",
                f"drain: {self.drained_at - self.window_end:.3f} s after the window closed"]
