"""Fabric-served inference: session-sticky KV affinity, endpoint-level
continuous batching (decode coalescer), cache_bytes admission, failover
re-prefill, and the affinity_hint fallback regression."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import (
    Forwarder,
    FunctionService,
    MetricsRegistry,
    TaskEnvelope,
    TaskFuture,
)
from repro.core.containers import ContainerSpec
from repro.models.model import Model
from repro.serving.engine import ServeEngine
from repro.serving.fabric import (
    CacheAdmissionError,
    DecodeCoalescer,
    ModelHost,
    reset_serving,
    serve_model,
)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_reduced("qwen1.5-0.5b").with_(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(autouse=True)
def _clean_specs():
    yield
    reset_serving()


def _greedy_reference(model, params, prompt, n_new):
    toks = list(np.asarray(prompt, np.int32))
    out = []
    for _ in range(n_new):
        h, _ = model.forward(params, {"tokens": jnp.asarray([toks], jnp.int32)})
        logits = model._logits(params, h)[0, -1]
        nxt = int(jnp.argmax(logits))
        out.append(nxt)
        toks.append(nxt)
    return out


def _fabric(model, params, n_endpoints=2, **serve_kw):
    svc = FunctionService()
    spec = ContainerSpec(
        name="jit", capabilities={"cpu", "jit"}, min_workers=0, max_workers=8
    )
    endpoints = [
        svc.make_endpoint(f"site{i}", n_executors=1, containers=[spec])
        for i in range(n_endpoints)
    ]
    serve_kw.setdefault("max_len", 48)
    serve_kw.setdefault("max_sessions", 6)
    client = serve_model(svc, model, params, name="qwen", **serve_kw)
    return svc, endpoints, client


# ---------------------------------------------------------------- tentpole
def test_fabric_generation_matches_reference(small_model):
    model, params = small_model
    svc, _, client = _fabric(model, params, n_endpoints=1)
    try:
        prompt = np.random.default_rng(0).integers(0, model.cfg.vocab, 6)
        toks = client.generate(prompt, max_new_tokens=5)
        assert toks == _greedy_reference(model, params, prompt, 5)
        snap = svc.metrics.snapshot()
        # 4 decode steps, all served from the resident cache slot
        assert snap["counters"]["serving.affinity_hits"] == 4
        assert snap["counters"]["serving.prefills"] == 1
        assert snap["histograms"]["serving.ttft_s"]["count"] == 1
    finally:
        svc.shutdown()


def test_concurrent_sessions_coalesce(small_model):
    model, params = small_model
    svc, _, client = _fabric(model, params, n_endpoints=1, window_s=0.05)
    try:
        results = {}

        def user(k, prompt):
            results[k] = client.generate(prompt, max_new_tokens=4)

        rng = np.random.default_rng(1)
        prompts = {k: rng.integers(0, model.cfg.vocab, 5) for k in range(4)}
        threads = [
            threading.Thread(target=user, args=(k, p)) for k, p in prompts.items()
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for k, p in prompts.items():
            assert results[k] == _greedy_reference(model, params, p, 4)
        snap = svc.metrics.snapshot()["counters"]
        decodes = snap["serving.affinity_hits"]  # 3 per session
        # continuous batching: fewer kernel invocations than decode tasks
        assert snap["serving.decode_batches"] < decodes
        assert svc.metrics.histogram("serving.merged_per_step").percentile(100) > 1
    finally:
        svc.shutdown()


def test_sessions_stick_to_one_endpoint(small_model):
    model, params = small_model
    svc, _, client = _fabric(model, params, n_endpoints=2)
    try:
        sessions = []

        def user(prompt):
            s = client.session(prompt)
            list(s.stream(4))
            sessions.append(s)

        rng = np.random.default_rng(2)
        threads = [
            threading.Thread(target=user, args=(rng.integers(0, model.cfg.vocab, 5),))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for s in sessions:
            assert len(set(s.endpoints)) == 1, s.endpoints  # sticky
            assert s.migrations == 0
        snap = svc.metrics.snapshot()["counters"]
        assert snap["forwarder.session_hits"] > 0
        assert snap.get("serving.cache_migrations", 0) == 0
    finally:
        svc.shutdown()


def test_session_failover_reprefills_on_survivor(small_model):
    model, params = small_model
    svc, endpoints, client = _fabric(model, params, n_endpoints=2)
    by_id = {e.endpoint_id: e for e in endpoints}
    try:
        prompt = np.random.default_rng(3).integers(0, model.cfg.vocab, 6)
        s = client.session(prompt)
        s.step()
        home = s.endpoints[-1]
        by_id[home].kill()
        assert home in svc.forwarder.check_endpoints()
        s.step()
        s.step()
        assert s.migrations == 1
        assert set(s.endpoints[-2:]) != {home}  # moved to the survivor
        assert s.tokens == _greedy_reference(model, params, prompt, 4)
        snap = svc.metrics.snapshot()["counters"]
        assert snap["serving.cache_migrations"] == 1
        assert snap["forwarder.session_evictions"] == 1
    finally:
        svc.shutdown()


def test_unbatched_host_matches_reference(small_model):
    """The per-request baseline path (private batch-1 caches) decodes the
    same tokens as the reference — the bench's 2x claim compares equals."""
    model, params = small_model
    host = ModelHost(model, params, max_len=48, max_sessions=2, batching=False)
    prompt = np.random.default_rng(4).integers(0, model.cfg.vocab, 6)
    toks = [host.prefill("s1", prompt)]
    history = list(prompt) + toks
    for _ in range(3):
        nxt, migrated = host.decode("s1", history)
        assert not migrated
        toks.append(nxt)
        history.append(nxt)
    assert toks == _greedy_reference(model, params, prompt, 4)


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_recurrent_family_host_matches_reference(arch):
    """ssm and hybrid families always serve unbatched; hybrid's shared
    attention KV must decode inside its max_len cache like dense KV does."""
    model = Model(get_reduced(arch).with_(dtype="float32"))
    params = model.init(jax.random.PRNGKey(0))
    host = ModelHost(model, params, max_len=48, max_sessions=2)
    assert not host.batching
    prompt = np.random.default_rng(4).integers(0, model.cfg.vocab, 6)
    toks = [host.prefill("s1", prompt)]
    history = list(prompt) + toks
    for _ in range(3):
        nxt, _ = host.decode("s1", history)
        toks.append(nxt)
        history.append(nxt)
    assert toks == _greedy_reference(model, params, prompt, 4)


# ------------------------------------------------------------- admission
def test_cache_bytes_admission_control(small_model):
    from repro.serving.kv_cache import cache_bytes

    model, params = small_model
    per_seq = cache_bytes(model.cfg, 1, 48)
    metrics = MetricsRegistry()
    host = ModelHost(
        model, params, max_len=48, max_sessions=8,
        cache_bytes_budget=2 * per_seq, metrics=metrics,
    )
    assert host.n_slots == 2  # budget, not max_sessions, is the binding cap
    prompt = np.arange(4, dtype=np.int32)
    host.prefill("a", prompt)
    host.prefill("b", prompt)
    with pytest.raises(CacheAdmissionError):
        host.prefill("c", prompt)
    assert metrics.counter("serving.admission_rejects").value == 1
    assert host.release("a")
    host.prefill("c", prompt)  # freed slot admits the new session
    assert metrics.gauge("serving.cache_bytes").value == 2 * per_seq


def test_kv_block_counters_match_hand_count(small_model):
    """Each batched step counts the decode kernel's K/V blocks per layer:
    every slot's ceil((pos + 1) / block), idle slots included, against the
    blocks the cache holds."""
    model, params = small_model
    metrics = MetricsRegistry()
    host = ModelHost(model, params, max_len=1100, max_sessions=3, metrics=metrics)
    # 4 KV heads of 16 in float32: 256 bytes a position, so 512-position blocks
    assert host.block_k == 512
    rng = np.random.default_rng(5)
    host.prefill("long", rng.integers(0, model.cfg.vocab, 1022))
    host.prefill("short", rng.integers(0, model.cfg.vocab, 5))
    for _ in range(3):  # "long" decodes at 1022, 1023, 1024; the others stay
        host.decode("long", [])
    # long: 2, 2, 3 blocks; short: 1 each step; the unused slot: 1 each step
    assert metrics.counter("serving.kv_blocks_read").value == 7 + 3 + 3
    # 3 steps x 3 slots x ceil(1100 / 512) blocks
    assert metrics.counter("serving.kv_blocks_cached").value == 27


# ------------------------------------------------------------- coalescer
def test_decode_coalescer_merges_concurrent_submits():
    calls = []
    barrier = threading.Barrier(4)

    def step(slots):
        calls.append(list(slots))
        time.sleep(0.01)
        return {s: 100 + s for s in slots}

    metrics = MetricsRegistry()
    co = DecodeCoalescer(step, window_s=0.2, target_fn=lambda: 4, metrics=metrics)
    out = {}

    def submit(slot):
        barrier.wait()
        out[slot] = co.submit(slot)

    threads = [threading.Thread(target=submit, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert out == {0: 100, 1: 101, 2: 102, 3: 103}
    # at least one merged kernel invocation
    assert metrics.counter("serving.decode_batches").value < 4
    assert metrics.histogram("serving.merged_per_step").sum == 4
    assert max(len(c) for c in calls) > 1


def test_decode_coalescer_propagates_step_errors():
    def step(slots):
        raise RuntimeError("kernel exploded")

    co = DecodeCoalescer(step, window_s=0.01)
    with pytest.raises(RuntimeError, match="kernel exploded"):
        co.submit(0)
    # leadership must be released for the next caller
    co2 = DecodeCoalescer(lambda slots: {s: 7 for s in slots}, window_s=0.01)
    assert co2.submit(1) == 7


# ------------------------------------------------- site-aware dispatch
def test_site_aware_function_sees_its_endpoint():
    svc = FunctionService()
    eps = [svc.make_endpoint(f"s{i}", workers_per_executor=2) for i in range(2)]
    try:
        fid = svc.register_function(
            lambda _payload, site: site.endpoint_id, name="where", public=True,
            site_aware=True,
        )
        for ep in eps:
            assert svc.run(
                fid, None, endpoint_id=ep.endpoint_id, sync=True, timeout=10
            ) == ep.endpoint_id
    finally:
        svc.shutdown()


# ----------------------------------------- affinity / session routing
class FakeEndpoint:
    def __init__(self, eid, capacity=4, alive=True):
        self.endpoint_id = eid
        self._capacity = capacity
        self._alive = alive
        self.submitted = []

    def is_alive(self, max_heartbeat_age_s=None):
        return self._alive

    def capacity(self):
        return self._capacity

    def has_warm(self, key):
        return False

    def submit(self, env, future):
        self.submitted.append(env)


def _affinity_hits(fwd):
    return fwd.metrics.counter("forwarder.affinity_hits").value


def test_affinity_hint_falls_back_when_endpoint_dead():
    fwd = Forwarder(policy="least_outstanding", seed=0)
    dead, live = FakeEndpoint("dead", alive=False), FakeEndpoint("live")
    fwd.register(dead)
    fwd.register(live)
    try:
        env = TaskEnvelope(task_id="t0", function_id="f", payload=b"",
                           affinity_hint="dead")
        eid = fwd.submit(env, TaskFuture("t0"))
        assert eid == "live"
        assert _affinity_hits(fwd) == 0  # fallback must not count as a hit
    finally:
        fwd.shutdown()


def test_affinity_hint_falls_back_at_capacity():
    fwd = Forwarder(policy="least_outstanding", seed=0)
    a, b = FakeEndpoint("a", capacity=1), FakeEndpoint("b")
    fwd.register(a)
    fwd.register(b)
    try:
        # saturate a: one outstanding task == its full capacity
        fwd.submit(TaskEnvelope(task_id="t0", function_id="f", payload=b""),
                   TaskFuture("t0"), endpoint_id="a")
        env = TaskEnvelope(task_id="t1", function_id="f", payload=b"",
                           affinity_hint="a")
        eid = fwd.submit(env, TaskFuture("t1"))
        assert eid == "b"
        assert _affinity_hits(fwd) == 0
    finally:
        fwd.shutdown()


def test_pinned_submission_binds_session():
    fwd = Forwarder(policy="least_outstanding", seed=0)
    fwd.register(FakeEndpoint("a"))
    fwd.register(FakeEndpoint("b"))
    try:
        env = TaskEnvelope(task_id="t0", function_id="f", payload=b"",
                           session_id="sess")
        fwd.submit(env, TaskFuture("t0"), endpoint_id="b")
        # residency established: the next unpinned step follows the cache
        assert fwd.sessions.lookup("sess") == "b"
        env2 = TaskEnvelope(task_id="t1", function_id="f", payload=b"",
                            session_id="sess")
        assert fwd.submit(env2, TaskFuture("t1")) == "b"
    finally:
        fwd.shutdown()


def test_session_sticks_even_at_capacity_until_death():
    """Session affinity is harder than affinity_hint: saturation doesn't
    move a session (its KV slot is there); only death rebinds it."""
    fwd = Forwarder(policy="least_outstanding", seed=0)
    a, b = FakeEndpoint("a", capacity=1), FakeEndpoint("b", capacity=1)
    fwd.register(a)
    fwd.register(b)
    try:
        def sub(i):
            env = TaskEnvelope(task_id=f"t{i}", function_id="f", payload=b"",
                               session_id="sess")
            return fwd.submit(env, TaskFuture(f"t{i}"))

        home = sub(0)
        # futures never resolve: the home endpoint is saturated, yet the
        # session's tasks keep landing there
        assert sub(1) == home and sub(2) == home
        assert fwd.metrics.counter("forwarder.session_hits").value == 2
        (a if home == "a" else b)._alive = False
        assert home in fwd.check_endpoints()
        moved = sub(3)
        assert moved != home
        assert fwd.sessions.lookup("sess") == moved
        assert fwd.metrics.counter("forwarder.session_moves").value == 0
        assert fwd.metrics.counter("forwarder.session_evictions").value == 1
    finally:
        fwd.shutdown()


# ------------------------------------------------------- engine metrics
def test_engine_exports_serving_metrics(small_model):
    model, params = small_model
    metrics = MetricsRegistry()
    engine = ServeEngine(model, params, max_batch=2, max_len=32, metrics=metrics)
    rng = np.random.default_rng(5)
    for _ in range(2):
        engine.submit(rng.integers(0, model.cfg.vocab, 4), max_new_tokens=3)
    engine.run_until_drained(timeout=120)
    snap = metrics.snapshot()
    assert snap["histograms"]["serving.ttft_s"]["count"] == 2
    assert snap["counters"]["serving.tokens_generated"] == 6
    assert snap["counters"]["serving.decode_batches"] >= 2
    assert snap["gauges"]["serving.batch_occupancy"] is not None
