"""Spans in the MetricsRegistry: off by default and inert, on into a bounded
ring; each tagged with its task, its parent span and its thread, stamped on
the monotonic clock a profiler trace is read on."""
import os
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import FunctionService, MetricsRegistry
from repro.core import metrics as metrics_mod
from repro.core.containers import ContainerSpec
from repro.core.metrics import NO_SPAN, current_span
from repro.models.model import Model
from repro.serving.fabric import reset_serving, serve_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _in_thread(fn):
    """Run `fn` on a fresh thread (a clean thread-local) and return its value."""
    out = {}
    t = threading.Thread(target=lambda: out.setdefault("v", fn()))
    t.start()
    t.join()
    return out["v"]


def _matmul_sum(doc):
    x = doc["x"]
    return {"y": jnp.sum(x @ x.T)}


def _fn_service():
    svc = FunctionService()
    svc.make_endpoint("local", n_executors=1, workers_per_executor=1)
    fid = svc.register_function(_matmul_sum, name="matmul_sum", jax_jit=True)
    return svc, fid


# ---------------------------------------------------------------- registry
def test_spans_off_record_nothing():
    reg = MetricsRegistry()
    lock = threading.Lock()

    def work():
        assert reg.span("a") is NO_SPAN and reg.task("t") is NO_SPAN
        assert reg.locked(lock, "w") is lock
        with reg.task("t"), reg.span("a"), reg.locked(lock, "w"):
            assert current_span("b") is NO_SPAN
        # nothing was written to this thread's context
        return dict(vars(metrics_mod._context))

    assert _in_thread(work) == {}
    assert reg.take_spans() == []
    assert "telemetry.spans_dropped" not in reg.snapshot()["counters"]


def test_spans_on_record_name_task_parent_thread():
    reg = MetricsRegistry()
    reg.enable_spans(64)
    lock = threading.Lock()

    def work():
        with reg.span("untasked"):
            pass
        with reg.task("task-1"):
            with reg.span("outer"):
                with reg.locked(lock, "wait"):
                    with current_span("inner"):
                        time.sleep(0.001)
        with reg.span("after"):
            pass
        return threading.get_ident()

    ident = _in_thread(work)
    spans = {s.name: s for s in reg.take_spans()}
    assert set(spans) == {"untasked", "outer", "wait", "inner", "after"}
    assert all(s.thread == ident for s in spans.values())
    assert (spans["untasked"].task, spans["untasked"].parent) == (None, None)
    assert (spans["outer"].task, spans["outer"].parent) == ("task-1", None)
    assert (spans["wait"].task, spans["wait"].parent) == ("task-1", "outer")
    assert (spans["inner"].task, spans["inner"].parent) == ("task-1", "outer")
    # the task scope ended: later spans carry no task
    assert spans["after"].task is None
    o, i = spans["outer"], spans["inner"]
    assert o.start_ns <= i.start_ns and i.end_ns <= o.end_ns
    assert i.end_ns - i.start_ns >= 1_000_000
    assert reg.take_spans() == []  # taking empties the ring


def test_span_ring_drops_the_oldest_and_counts():
    reg = MetricsRegistry()
    reg.enable_spans(3)
    for i in range(5):
        with reg.span(f"s{i}"):
            pass
    assert [s.name for s in reg.take_spans()] == ["s2", "s3", "s4"]
    assert reg.counter("telemetry.spans_dropped").value == 2


# ---------------------------------------------------------------- fabric
def test_jit_task_spans_share_its_id_in_order():
    svc, fid = _fn_service()
    try:
        doc = {"x": np.ones((8, 8), np.float32)}
        svc.run(fid, doc).result(60)  # compiled before spans go on
        svc.metrics.enable_spans()
        fut = svc.run(fid, doc)
        fut.result(60)
        spans = [s for s in svc.metrics.take_spans() if s.task == fut.task_id]
    finally:
        svc.shutdown()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    order = ["service.submit", "worker.unpack", "worker.call", "worker.repack"]
    for name in order + ["worker.dispatch", "worker.block"]:
        assert len(by_name.get(name, ())) == 1, (name, sorted(by_name))
    starts = [by_name[n][0].start_ns for n in order]
    assert starts == sorted(starts)
    call = by_name["worker.call"][0]
    dispatch, block = by_name["worker.dispatch"][0], by_name["worker.block"][0]
    for s in (dispatch, block):
        assert s.parent == "worker.call" and s.thread == call.thread
        assert call.start_ns <= s.start_ns and s.end_ns <= call.end_ns
    assert dispatch.end_ns <= block.start_ns
    # the endpoint's own spans for this task
    assert {"endpoint.dispatch", "endpoint.result"} <= set(by_name)


def test_concurrent_decodes_lead_and_follow():
    model = Model(get_reduced("qwen1.5-0.5b").with_(dtype="float32"))
    params = model.init(jax.random.PRNGKey(0))
    svc = FunctionService()
    spec = ContainerSpec(name="jit", capabilities={"cpu", "jit"}, min_workers=0,
                         max_workers=8)
    svc.make_endpoint("site", n_executors=1, containers=[spec])
    client = serve_model(svc, model, params, name="qwen", max_len=48,
                         max_sessions=6, window_s=0.05)
    try:
        client.generate(np.arange(5), max_new_tokens=2)  # compile first
        svc.metrics.enable_spans()
        rng = np.random.default_rng(3)
        threads = [threading.Thread(target=client.generate,
                                    args=(rng.integers(0, model.cfg.vocab, 5), 4))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = svc.metrics.take_spans()
        counters = svc.metrics.snapshot()["counters"]
    finally:
        svc.shutdown()
        reset_serving()
    names = [s.name for s in spans]
    assert "serving.coalesce_lead" in names and "serving.coalesce_follow" in names
    assert names.count("serving.prefill") == 4 and names.count("serving.insert") == 4
    steps = [s for s in spans if s.name == "serving.step"]
    readbacks = [s for s in spans if s.name == "serving.readback"]
    assert steps and len(readbacks) == len(steps)
    assert all(r.parent == "serving.step" for r in readbacks)
    assert all(s.task is not None for s in spans if s.name.startswith("serving."))
    full = counters.get("serving.window_full", 0)
    expired = counters.get("serving.window_expired", 0)
    assert full + expired == counters["serving.decode_batches"] > 0


def test_spans_share_the_profiler_trace_clock():
    """A jitted call's worker.dispatch span, read on the monotonic clock,
    lies over the runtime's own PjitFunction event in a CPU profiler trace
    put on that clock by the benchmark's mark."""
    from bench import trace as tr

    svc, fid = _fn_service()
    log_dir = tempfile.mkdtemp(prefix="spans-trace-")
    try:
        doc = {"x": np.ones((64, 64), np.float32)}
        svc.run(fid, doc).result(60)
        svc.metrics.enable_spans()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tr.MARK):
                mark_ns = time.monotonic_ns()
            t0 = time.monotonic()
            svc.run(fid, doc).result(60)
            t1 = time.monotonic()
        finally:
            jax.profiler.stop_trace()
        spans = [s for s in svc.metrics.take_spans() if s.name == "worker.dispatch"]
        trace = tr.load(tr.find_xplane(log_dir), mark_ns, t0, t1)
    finally:
        svc.shutdown()
        shutil.rmtree(log_dir, ignore_errors=True)
    pjit = [e for e in trace.host if e.name.startswith("PjitFunction(")]
    assert len(spans) == 1 and pjit
    a, b = spans[0].start_ns * 1e-9, spans[0].end_ns * 1e-9
    best = min(pjit, key=lambda e: abs(e.start - a) + abs(e.start + e.dur - b))
    assert abs(best.start - a) < 1e-3 and abs(best.start + best.dur - b) < 1e-3
