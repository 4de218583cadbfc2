"""Unit + property tests for core FaaS components."""
import time

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property-based cases skip without the dev extra
    from _hypothesis_stub import given, settings, st

from repro.core import (
    FunctionRegistry,
    HeartbeatMonitor,
    MemoCache,
    TaskEnvelope,
    WarmPool,
    hash_function,
    packb,
    payload_hash,
    stack_payloads,
    unpackb,
    unstack_results,
)
from repro.core.batching import group_by_function
from repro.core.heartbeat import LatencyTracker


# ---------------------------------------------------------------- registry
def test_hash_function_stable_and_content_sensitive():
    def f(x):
        return x + 1

    def g(x):
        return x + 2

    assert hash_function(f) == hash_function(f)
    assert hash_function(f) != hash_function(g)
    assert hash_function(f, static="a") != hash_function(f, static="b")


def test_hash_function_closure_sensitivity():
    def make(k):
        def h(x):
            return x + k

        return h

    assert hash_function(make(1)) != hash_function(make(2))


def test_registry_idempotent_and_lookup():
    reg = FunctionRegistry()
    f = lambda d: d  # noqa: E731
    fid1 = reg.register(f, name="id")
    fid2 = reg.register(f, name="id")
    assert fid1 == fid2
    assert reg.get(fid1).name == "id"
    with pytest.raises(KeyError):
        reg.get("nope")


def test_authorized_requires_identity_match():
    """Regression: anonymous-owned functions used to be world-executable —
    ``authorized()`` treated owner="anonymous" as a wildcard. Ownership is a
    strict identity comparison now; ``public=True`` is the only open door."""
    reg = FunctionRegistry()
    private = reg.register(lambda d: d, name="private")          # owner=anonymous
    owned = reg.register(lambda d: d + 0, name="owned", owner="alice")
    shared = reg.register(lambda d: d + 1, name="shared", owner="alice", public=True)

    # the anonymous-owner default only opens the no-authority deployment
    assert reg.authorized(private, "anonymous")
    assert not reg.authorized(private, "mallory")
    # owners invoke their own functions; everyone else is rejected
    assert reg.authorized(owned, "alice")
    assert not reg.authorized(owned, "bob")
    assert not reg.authorized(owned, "anonymous")
    # public stays the explicit opt-in for cross-user execution
    assert reg.authorized(shared, "bob")


def test_registry_requirements_normalized():
    from repro.core import ResourceSpec

    reg = FunctionRegistry()
    fid = reg.register(lambda d: d, name="caps", requirements=("tpu", "cpu"))
    spec = reg.get(fid).requirements
    assert isinstance(spec, ResourceSpec)
    assert spec.capabilities == frozenset({"tpu", "cpu"})
    fid2 = reg.register(
        lambda d: d * 1, name="pref",
        requirements=ResourceSpec(frozenset({"jit"}), preferred_container="jit"),
    )
    assert reg.get(fid2).requirements.preferred_container == "jit"
    assert reg.get(fid2).requirements.satisfied_by({"cpu", "jit"})
    assert not reg.get(fid2).requirements.satisfied_by({"cpu"})


# ---------------------------------------------------------------- serializer
payload_leaf = st.one_of(
    st.integers(min_value=-(2**40), max_value=2**40),
    st.floats(allow_nan=False, allow_infinity=False, width=32),
    st.text(max_size=16),
    st.booleans(),
    st.none(),
    st.binary(max_size=32),
)
payload_tree = st.recursive(
    payload_leaf,
    lambda children: st.one_of(
        st.lists(children, max_size=4),
        st.dictionaries(st.text(max_size=6), children, max_size=4),
        st.tuples(children, children),
    ),
    max_leaves=12,
)


@given(payload_tree)
@settings(max_examples=80, deadline=None)
def test_serializer_roundtrip_property(tree):
    out = unpackb(packb(tree))

    def norm(x):
        if isinstance(x, tuple):
            return [norm(v) for v in x]
        if isinstance(x, list):
            return [norm(v) for v in x]
        if isinstance(x, dict):
            return {k: norm(v) for k, v in x.items()}
        return x

    assert norm(out) == norm(tree)


@given(payload_tree)
@settings(max_examples=50, deadline=None)
def test_payload_hash_deterministic(tree):
    assert payload_hash(tree) == payload_hash(tree)


def test_serializer_ndarray_roundtrip():
    for dt in (np.float32, np.int64, np.bool_, np.float16, np.uint8):
        arr = (np.arange(24).reshape(2, 3, 4) % 2).astype(dt)
        out = unpackb(packb({"a": arr}))["a"]
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == arr.dtype


def test_payload_hash_dict_order_invariant():
    a = {"x": 1, "y": np.ones(3)}
    b = {"y": np.ones(3), "x": 1}
    assert payload_hash(a) == payload_hash(b)


# ---------------------------------------------------------------- memoization
def test_memo_lru_eviction_and_stats():
    memo = MemoCache(max_entries=2)
    memo.put("f", "a", 1)
    memo.put("f", "b", 2)
    memo.put("f", "c", 3)  # evicts ("f","a")
    hit, _ = memo.get("f", "a")
    assert not hit
    hit, v = memo.get("f", "c")
    assert hit and v == 3
    s = memo.stats()
    assert s["entries"] == 2 and s["hits"] == 1 and s["misses"] == 1


def test_memo_invalidate():
    memo = MemoCache()
    memo.put("f", "a", 1)
    memo.put("g", "a", 2)
    assert memo.invalidate("f") == 1
    assert len(memo) == 1


# ---------------------------------------------------------------- warming
def test_warm_pool_hit_miss_ttl():
    pool = WarmPool(ttl_s=0.05, max_entries=4)
    calls = []

    def compile_fn():
        calls.append(1)
        return lambda d: d

    _, cold, _ = pool.get_or_compile(("f", "c"), compile_fn)
    assert cold and len(calls) == 1
    _, cold, _ = pool.get_or_compile(("f", "c"), compile_fn)
    assert not cold and len(calls) == 1  # warm hit
    time.sleep(0.08)
    _, cold, _ = pool.get_or_compile(("f", "c"), compile_fn)
    assert cold and len(calls) == 2  # TTL expired -> cold again
    assert pool.stats()["cold_starts"] == 2


def test_warm_pool_lru_bound():
    pool = WarmPool(ttl_s=100, max_entries=2)
    for i in range(4):
        pool.get_or_compile(("f", i), lambda: i)
    assert len(pool) == 2
    assert pool.stats()["evictions"] == 2


# scheduler policy/filter coverage lives in tests/test_scheduler.py


# ---------------------------------------------------------------- batching
@given(st.lists(st.integers(0, 100), min_size=1, max_size=16))
@settings(max_examples=40, deadline=None)
def test_stack_unstack_no_loss_no_dup(values):
    payloads = [{"x": np.full(3, v, np.int64), "tag": "same"} for v in values]
    stacked = stack_payloads(payloads)
    assert stacked["x"].shape == (len(values), 3)
    outs = unstack_results(stacked, len(values))
    got = [int(o["x"][0]) for o in outs]
    assert got == values  # order preserved, nothing lost or duplicated


def test_stack_rejects_mismatched_structure():
    with pytest.raises(ValueError):
        stack_payloads([{"a": np.ones(2)}, {"b": np.ones(2)}])
    with pytest.raises(ValueError):
        stack_payloads([{"a": np.ones(2), "t": 1}, {"a": np.ones(2), "t": 2}])


def test_group_by_function():
    envs = [
        TaskEnvelope(task_id=str(i), function_id="f" if i % 2 else "g", payload=b"")
        for i in range(6)
    ]
    groups = group_by_function(envs)
    assert len(groups) == 2
    assert sum(len(v) for v in groups.values()) == 6


# ---------------------------------------------------------------- heartbeat
def test_heartbeat_dead_detection():
    mon = HeartbeatMonitor(interval_s=0.01, threshold=2.0)
    mon.register("a")
    mon.register("b")
    for _ in range(3):
        mon.beat("b")
        time.sleep(0.01)
    assert mon.dead() == []  # the first pass has nothing to judge against
    mon.beat("b")
    dead = mon.dead()  # "a" was past the limit at that pass, silent since
    assert "a" in dead and "b" not in dead
    mon.suspend("a")
    assert "a" not in mon.dead()  # suspended are not re-reported
    assert mon.revived() == []
    mon.beat("a")  # the death was a false positive: "a" beats again
    assert mon.revived() == ["a"]
    mon.resume("a")
    assert mon.revived() == [] and "a" not in mon.dead()


def test_heartbeat_stall_of_the_process_is_not_a_death():
    """A stall of the whole process stops beats and watchdog passes alike.
    When the pass runs first after it, it finds the beat past the death
    limit (0.5 s), but the previous pass did not; the beat that follows is
    newer than that pass, so no death, however long or frequent the stalls.
    An executor that stops beating is dead at the first pass after one that
    found it past the limit."""
    mon = HeartbeatMonitor(interval_s=0.25, threshold=2.0)
    mon.register("a", now=0.0)
    mon.beat("a", now=0.2)
    assert mon.dead(now=0.26) == []
    assert mon.dead(now=0.75) == []  # stall 0.27-0.75: the beat is 0.55 s old
    mon.beat("a", now=0.751)
    for t in (1.0, 1.6, 2.2, 2.8):  # stalls of 0.6 s, one beat between each
        assert mon.dead(now=t) == []
        mon.beat("a", now=t + 0.001)
    assert mon.dead(now=3.05) == []  # the beat at 2.801 is newer than 2.8
    assert mon.dead(now=3.3) == []  # 0.499 s old: inside the limit
    assert mon.dead(now=3.55) == []  # past the limit, but not at 3.3
    assert mon.dead(now=3.8) == ["a"]  # past it at 3.55 too, silent since


def test_latency_tracker_p95():
    t = LatencyTracker()
    assert t.p95() is None
    for v in range(100):
        t.record(v / 100)
    assert 0.9 <= t.p95() <= 0.99
