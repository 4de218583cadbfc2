"""Forwarder fabric tier: routing policies, capacity sharding, failover."""
import time

import pytest

from repro.core import Forwarder, FunctionService, TaskEnvelope, TaskFuture


class FakeEndpoint:
    def __init__(self, eid, capacity=4, warm=(), alive=True):
        self.endpoint_id = eid
        self._capacity = capacity
        self._warm = set(warm)
        self._alive = alive
        self.submitted = []

    def is_alive(self, max_heartbeat_age_s=None):
        return self._alive

    def capacity(self):
        return self._capacity

    def has_warm(self, key):
        return key in self._warm

    def submit(self, env, future):
        self.submitted.append(env)


def _env(i=0, fn="f"):
    return TaskEnvelope(task_id=f"t{i}", function_id=fn, payload=b"")


def _submit(fwd, ep_hint=None, i=0):
    fut = TaskFuture(f"t{i}")
    eid = fwd.submit(_env(i), fut, endpoint_id=ep_hint)
    return eid, fut


@pytest.fixture()
def fwd_factory():
    created = []

    def make(policy, endpoints, **kwargs):
        f = Forwarder(policy=policy, seed=0, **kwargs)
        for ep in endpoints:
            f.register(ep)
        created.append(f)
        return f

    yield make
    for f in created:
        f.shutdown()


# ---------------------------------------------------------------- routing
def test_unknown_policy_rejected():
    with pytest.raises(ValueError):
        Forwarder(policy="nope")

def test_least_outstanding_spreads_load(fwd_factory):
    a, b = FakeEndpoint("a"), FakeEndpoint("b")
    fwd = fwd_factory("least_outstanding", [a, b])
    picks = [_submit(fwd, i=i)[0] for i in range(4)]  # futures never complete
    assert sorted(picks) == ["a", "a", "b", "b"]


def test_least_outstanding_prefers_idle_endpoint(fwd_factory):
    a, b = FakeEndpoint("a"), FakeEndpoint("b")
    fwd = fwd_factory("least_outstanding", [a, b])
    eid0, fut0 = _submit(fwd, i=0)
    eid1, fut1 = _submit(fwd, i=1)
    fut1.set_result(None)  # the second endpoint is idle again
    eid2, _ = _submit(fwd, i=2)
    assert eid2 == eid1 != eid0


def test_latency_aware_prefers_fast_endpoint(fwd_factory):
    a, b = FakeEndpoint("a"), FakeEndpoint("b")
    fwd = fwd_factory("latency_aware", [a, b])
    fwd._records["a"].latency_ewma = 0.5
    fwd._records["b"].latency_ewma = 0.01
    assert fwd.choose(_env()).endpoint_id == "b"


def test_latency_aware_explores_unmeasured_first(fwd_factory):
    a, b = FakeEndpoint("a"), FakeEndpoint("b")
    fwd = fwd_factory("latency_aware", [a, b])
    fwd._records["a"].latency_ewma = 0.001  # fast, but b is unmeasured
    assert fwd.choose(_env()).endpoint_id == "b"


def test_warm_affinity_prefers_warm_endpoint(fwd_factory):
    cold = FakeEndpoint("cold")
    warm = FakeEndpoint("warm", warm=[("f", "default")])
    fwd = fwd_factory("warm_affinity", [cold, warm])
    assert fwd.choose(_env(fn="f")).endpoint_id == "warm"
    assert fwd.choose(_env(fn="other")).endpoint_id in ("cold", "warm")


def test_random_uses_all_endpoints(fwd_factory):
    eps = [FakeEndpoint(f"e{i}") for i in range(3)]
    fwd = fwd_factory("random", eps)
    picks = {fwd.choose(_env(i)).endpoint_id for i in range(60)}
    assert picks == {"e0", "e1", "e2"}


def test_dead_endpoints_excluded_from_routing(fwd_factory):
    a, b = FakeEndpoint("a", alive=False), FakeEndpoint("b")
    fwd = fwd_factory("random", [a, b])
    assert all(fwd.choose(_env(i)).endpoint_id == "b" for i in range(10))


def test_pinned_submit_goes_to_requested_endpoint(fwd_factory):
    a, b = FakeEndpoint("a"), FakeEndpoint("b")
    fwd = fwd_factory("least_outstanding", [a, b])
    for i in range(3):
        eid, _ = _submit(fwd, ep_hint="b", i=i)
        assert eid == "b"
    assert len(b.submitted) == 3 and not a.submitted
    with pytest.raises(KeyError):
        _submit(fwd, ep_hint="nope")


def test_no_live_endpoint_raises(fwd_factory):
    fwd = fwd_factory("random", [FakeEndpoint("a", alive=False)])
    with pytest.raises(RuntimeError):
        _submit(fwd)


# ---------------------------------------------------------------- sharding
def test_shard_proportional_to_capacity(fwd_factory):
    a = FakeEndpoint("a", capacity=2)
    b = FakeEndpoint("b", capacity=6)
    fwd = fwd_factory("random", [a, b])
    alloc = dict(fwd.shard(8))
    assert alloc == {"a": 2, "b": 6}
    # remainders are distributed and the allocation always covers n
    for n in (1, 3, 7, 100):
        assert sum(c for _, c in fwd.shard(n)) == n


def test_shard_skips_dead_endpoints(fwd_factory):
    a = FakeEndpoint("a", capacity=4, alive=False)
    b = FakeEndpoint("b", capacity=4)
    fwd = fwd_factory("random", [a, b])
    assert dict(fwd.shard(6)) == {"b": 6}


def test_map_shards_by_advertised_capacity():
    svc = FunctionService()
    big = svc.make_endpoint("big", n_executors=3, workers_per_executor=2)
    small = svc.make_endpoint("small", n_executors=1, workers_per_executor=2)

    def ident(doc):
        return doc

    fid = svc.register_function(ident)
    outs = svc.map(fid, [{"i": i} for i in range(8)], timeout=30)
    assert [o["i"] for o in outs] == list(range(8))  # order preserved
    routed = svc.forwarder.stats()["endpoints"]
    assert routed[big.endpoint_id]["routed"] == 6
    assert routed[small.endpoint_id]["routed"] == 2
    svc.shutdown()


# ---------------------------------------------------------------- failover
def _sleepy(doc):
    time.sleep(doc.get("t", 0.03))
    return {"i": doc.get("i", -1)}


def test_endpoint_death_fails_over_to_survivor():
    svc = FunctionService(policy="least_outstanding")
    svc.forwarder.liveness_threshold_s = 0.2
    svc.forwarder.watchdog_interval_s = 0.02
    ep_a = svc.make_endpoint("fo-a", n_executors=1, workers_per_executor=2)
    svc.make_endpoint("fo-b", n_executors=1, workers_per_executor=2)
    fid = svc.register_function(_sleepy)
    futs = [svc.run(fid, {"i": i, "t": 0.08}) for i in range(10)]
    time.sleep(0.05)
    ep_a.kill()
    results = [f.result(timeout=30) for f in futs]
    assert sorted(r["i"] for r in results) == list(range(10))
    assert svc.forwarder.failovers > 0
    assert svc.forwarder.stats()["endpoints"][ep_a.endpoint_id]["dead"]
    svc.shutdown()


def test_death_with_no_survivor_raises():
    svc = FunctionService()
    svc.forwarder.liveness_threshold_s = 0.2
    svc.forwarder.watchdog_interval_s = 0.02
    ep = svc.make_endpoint("solo", n_executors=1, workers_per_executor=1)
    fid = svc.register_function(_sleepy)
    fut = svc.run(fid, {"i": 0, "t": 0.5})
    ep.kill()
    with pytest.raises(RuntimeError, match="lost"):
        fut.result(timeout=10)
    assert svc.forwarder.orphaned == 1
    svc.shutdown()


def test_false_positive_death_resurrects_on_fresh_heartbeat():
    svc = FunctionService()
    svc.forwarder.watchdog_interval_s = 0.01
    ep = svc.make_endpoint("fp", n_executors=1, workers_per_executor=1)
    fid = svc.register_function(_sleepy)
    svc.run(fid, {"i": 0, "t": 0.0}).result(10)
    # running, but every heartbeat looks stale
    ep.is_alive = lambda max_heartbeat_age_s=None: max_heartbeat_age_s is None
    deadline = time.monotonic() + 2
    while not svc.forwarder.stats()["endpoints"][ep.endpoint_id]["dead"]:
        assert time.monotonic() < deadline, "watchdog never marked endpoint dead"
        time.sleep(0.01)
    del ep.is_alive  # heartbeat is fresh again
    deadline = time.monotonic() + 2
    while svc.forwarder.stats()["endpoints"][ep.endpoint_id]["dead"]:
        assert time.monotonic() < deadline, "endpoint was never resurrected"
        time.sleep(0.01)
    out = svc.run(fid, {"i": 7, "t": 0.0}, sync=True, timeout=10)
    assert out["i"] == 7
    svc.shutdown()


class StallingEndpoint(FakeEndpoint):
    """Running, with its last heartbeat at `beat_at` (monotonic clock)."""

    def __init__(self, eid):
        super().__init__(eid)
        self.beat_at = time.monotonic()

    def is_alive(self, max_heartbeat_age_s=None):
        if max_heartbeat_age_s is None:
            return self._alive
        return self._alive and time.monotonic() - self.beat_at <= max_heartbeat_age_s


def test_one_stale_pass_keeps_endpoint_and_its_sessions(fwd_factory):
    """The first watchdog pass after a stall of the whole process can find
    any heartbeat past the threshold. Calling that a death would evict the
    endpoint's sessions (their next step re-prefills elsewhere) and fail its
    tasks over, so an endpoint is dead only when the previous pass had
    already found it past the threshold and it has not beaten since."""
    a, b = StallingEndpoint("a"), StallingEndpoint("b")
    fwd = fwd_factory("least_outstanding", [a, b], watchdog_interval_s=3600,
                      liveness_threshold_s=0.2)
    fwd.sessions.bind("s", "a")
    assert fwd.check_endpoints() == []
    time.sleep(0.3)  # a stall: no beat, no pass
    assert fwd.check_endpoints() == []  # fine at the previous pass
    assert fwd.sessions.lookup("s") == "a"
    a.beat_at = b.beat_at = time.monotonic()  # both beat after it
    time.sleep(0.3)  # another stall
    assert fwd.check_endpoints() == []  # beat since the previous pass
    time.sleep(0.05)
    b.beat_at = time.monotonic()
    assert fwd.check_endpoints() == ["a"]  # past it then, silent since
    assert fwd.sessions.lookup("s") is None
    assert fwd.metrics.snapshot()["counters"]["forwarder.session_evictions"] == 1
    b._alive = False  # a stopped endpoint is dead at the next pass
    assert fwd.check_endpoints() == ["b"]


def _hold_gil(doc):
    """Hold the GIL `rounds` times for at least `s` seconds each in C (a sum
    over a range never yields), as an XLA compile or executable load can:
    every other thread stalls. The gaps between holds vary from 0.02 to
    0.2 s, so stalls start at every phase of the heartbeat and watchdog
    cycles. Returns the shortest hold."""
    n, held = 10**6, []
    while len(held) < doc["rounds"]:
        t0 = time.monotonic()
        sum(range(n))
        dt = time.monotonic() - t0
        if dt >= doc["s"]:
            held.append(dt)
            time.sleep(0.02 + 0.03 * (len(held) % 7))
        else:  # too short: grow toward the target and hold again
            n = int(n * 1.2 * doc["s"] / max(dt, 1e-6))
    return min(held)


def test_gil_stall_keeps_a_session_on_its_endpoint():
    """On a two-endpoint fabric, a session's task holds the GIL past the
    Forwarder's liveness threshold, several times: its endpoint is not
    declared dead, so its session is not evicted and its task does not fail
    over to the other endpoint."""
    svc = FunctionService()
    svc.forwarder.liveness_threshold_s = 0.6
    for i in range(2):
        svc.make_endpoint(f"gil{i}", n_executors=1, workers_per_executor=1)
    fid = svc.register_function(_hold_gil)
    assert svc.run(fid, {"s": 0.8, "rounds": 6}, session_id="s").result(60) >= 0.8
    counters = svc.metrics.snapshot()["counters"]
    assert counters.get("forwarder.session_evictions", 0) == 0
    assert counters.get("forwarder.failovers", 0) == 0
    assert counters.get("endpoint.executors_lost", 0) == 0
    svc.shutdown()


@pytest.mark.parametrize(
    "hold_s,rounds", [(0.3, 12), (0.4, 12), (0.8, 4), (2.5, 1)]
)
def test_gil_stall_is_not_a_death(hold_s, rounds):
    """Tasks that hold the GIL for one to two heartbeat intervals (0.3 and
    0.4 s: the executor's beat can then be past the 0.5 s death limit when
    the stall ends), past the death limit, or past the endpoint liveness
    threshold (2 s) stall the watchdogs too: nothing is declared dead, and
    the endpoint's one executor runs the next task. (Whether a stalled
    watchdog or the executor's heartbeat runs first after a stall is a race;
    several stalls make losing it likely.)"""
    svc = FunctionService()
    svc.make_endpoint("gil", n_executors=1, workers_per_executor=1)
    fid = svc.register_function(_hold_gil)
    held = svc.run(fid, {"s": hold_s, "rounds": rounds}).result(30)
    assert held >= hold_s
    assert svc.run(fid, {"s": 0.0, "rounds": 1}).result(10) < 0.5
    counters = svc.metrics.snapshot()["counters"]
    assert counters.get("endpoint.executors_lost", 0) == 0
    assert counters.get("forwarder.failovers", 0) == 0
    svc.shutdown()


def test_falsely_dead_executor_is_taken_back_when_it_beats_again():
    """An executor whose beats stop for longer than the death limit is
    declared dead; when it beats again the endpoint takes it back, so a
    non-elastic endpoint with one executor runs its next tasks instead of
    queueing them forever."""
    svc = FunctionService()
    ep = svc.make_endpoint("fp", n_executors=1, workers_per_executor=1)
    fid = svc.register_function(_sleepy)
    assert svc.run(fid, {"i": 0, "t": 0.0}).result(10) == {"i": 0}
    real_beat = ep.monitor.beat
    ep.monitor.beat = lambda executor_id, now=None: None  # beats held off
    counters = svc.metrics.snapshot
    deadline = time.monotonic() + 5
    while counters()["counters"].get("endpoint.executors_lost", 0) < 1:
        assert time.monotonic() < deadline, "watchdog never declared a death"
        time.sleep(0.02)
    assert ep.executors == {}  # no executor left to run the queue
    ep.monitor.beat = real_beat  # the executor beats again
    assert svc.run(fid, {"i": 1, "t": 0.0}).result(10) == {"i": 1}
    assert counters()["counters"].get("endpoint.executors_readmitted", 0) == 1
    assert ep.provider.status()["blocks"] == 1
    svc.shutdown()


def test_latency_ewma_recorded_after_completion():
    svc = FunctionService(policy="latency_aware")
    ep = svc.make_endpoint("lat", n_executors=1, workers_per_executor=2)
    fid = svc.register_function(_sleepy)
    svc.map(fid, [{"i": i, "t": 0.005} for i in range(4)], timeout=30)
    rec = svc.forwarder.stats()["endpoints"][ep.endpoint_id]
    assert rec["completed"] == 4
    assert rec["latency_ewma_s"] is not None and rec["latency_ewma_s"] > 0
    svc.shutdown()
