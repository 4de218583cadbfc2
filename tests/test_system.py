"""End-to-end behaviour of the FaaS platform (the paper's system)."""
import time

import numpy as np
import pytest

from repro.core import (
    AuthError,
    FunctionService,
    TaskState,
    TokenAuthority,
    SCOPE_INVOKE,
    SCOPE_REGISTER_ENDPOINT,
    SCOPE_REGISTER_FUNCTION,
)
from repro.core.futures import Timestamps


@pytest.fixture()
def service():
    svc = FunctionService()
    svc.make_endpoint("test-ep", n_executors=2, workers_per_executor=2, prefetch=2,
                      policy="least_loaded")
    yield svc
    svc.shutdown()


def _double(doc):
    return {"y": np.asarray(doc["x"]) * 2}


def test_register_and_run_roundtrip(service):
    fid = service.register_function(_double, name="double")
    fut = service.run(fid, {"x": np.arange(4)})
    out = fut.result(timeout=10)
    np.testing.assert_array_equal(out["y"], [0, 2, 4, 6])
    assert fut.state == TaskState.SUCCESS


def test_sync_invocation(service):
    fid = service.register_function(_double)
    out = service.run(fid, {"x": np.ones(3)}, sync=True, timeout=10)
    np.testing.assert_array_equal(out["y"], [2, 2, 2])


def test_latency_breakdown_monotonic(service):
    fid = service.register_function(_double)
    fut = service.run(fid, {"x": np.arange(2)})
    fut.result(10)
    b = fut.latency_breakdown()
    assert set(b) == {"t_c", "t_w", "t_m", "t_e", "total", "t_q", "t_p", "t_r"}
    assert all(v >= 0 for v in b.values())
    assert b["total"] >= b["t_e"]
    assert abs(b["total"] - sum(b[k] for k in ("t_c", "t_w", "t_m", "t_e"))) < 1e-6
    # t_m splits into the endpoint queue and the worker pickup
    assert b["t_q"] + b["t_p"] == b["t_m"]


@pytest.mark.parametrize("dispatched", [2.0, 0.0])
def test_latency_breakdown_splits_manager_time(dispatched):
    """t_m = t_q + t_p whether or not the endpoint stamped its dispatch (a
    task handed straight to an executor has no endpoint queue)."""
    ts = Timestamps(client_submit=0.5, service_in=0.75, endpoint_in=1.0,
                    dispatched=dispatched, exec_start=3.5, exec_end=4.0,
                    result_ready=4.25)
    b = ts.breakdown()
    assert b["t_q"] + b["t_p"] == b["t_m"] == 2.5
    assert b["t_q"] == (1.0 if dispatched else 0.0)
    assert b["t_r"] == 0.25


def test_map_many_tasks(service):
    fid = service.register_function(_double)
    outs = service.map(fid, [{"x": np.full(2, i)} for i in range(20)], timeout=30)
    assert [int(o["y"][0]) for o in outs] == [2 * i for i in range(20)]


def test_function_errors_surface(service):
    def boom(doc):
        raise ValueError("kaboom")

    fid = service.register_function(boom, name="boom")
    fut = service.run(fid, {}, max_retries=0)
    with pytest.raises(ValueError, match="kaboom"):
        fut.result(10)
    assert fut.state == TaskState.FAILED


def test_unknown_function_rejected(service):
    with pytest.raises(KeyError):
        service.run("deadbeef", {})


def test_jax_jit_function_warm_faster_than_cold(service):
    import jax.numpy as jnp

    def mm(doc):
        return {"z": jnp.dot(doc["a"], doc["a"].T).sum()}

    fid = service.register_function(mm, name="mm", jax_jit=True)
    p = {"a": np.ones((128, 128), np.float32)}
    t0 = time.monotonic()
    service.run(fid, p).result(60)
    cold = time.monotonic() - t0
    t0 = time.monotonic()
    service.run(fid, p).result(60)
    warm = time.monotonic() - t0
    assert warm < cold, (warm, cold)


def test_jax_jit_refused_compile_fails_task_without_warm_entry(service):
    """A compile the backend refuses fails the task; it neither counts as a
    cold start nor records a compile time for a program that never built."""
    import jax.numpy as jnp

    def mismatched(doc):
        return {"z": jnp.dot(doc["a"], doc["b"])}

    fid = service.register_function(mismatched, name="mismatched", jax_jit=True)
    fut = service.run(fid, {"a": np.ones((3, 4), np.float32),
                            "b": np.ones((5, 6), np.float32)}, max_retries=0)
    with pytest.raises(TypeError):
        fut.result(60)
    snap = service.metrics.snapshot()
    assert snap["counters"].get("warming.cold_starts", 0) == 0
    assert snap["histograms"].get("warming.compile_time_s", {}).get("count", 0) == 0


def test_auth_scopes_enforced():
    authority = TokenAuthority()
    svc = FunctionService(authority=authority)
    owner = authority.issue("alice", (SCOPE_REGISTER_FUNCTION, SCOPE_INVOKE,
                                      SCOPE_REGISTER_ENDPOINT))
    svc.make_endpoint("ep", n_executors=1, workers_per_executor=1, token=owner)
    fid = svc.register_function(_double, token=owner)

    invoker = authority.issue("bob", (SCOPE_INVOKE,))
    with pytest.raises(AuthError):
        svc.run(fid, {"x": np.ones(1)}, token=invoker)  # private function

    with pytest.raises(AuthError):
        svc.run(fid, {"x": np.ones(1)})  # no token

    out = svc.run(fid, {"x": np.ones(1)}, token=owner, sync=True, timeout=10)
    np.testing.assert_array_equal(out["y"], [2])
    svc.shutdown()


def test_public_function_cross_user():
    authority = TokenAuthority()
    svc = FunctionService(authority=authority)
    owner = authority.issue("alice", (SCOPE_REGISTER_FUNCTION, SCOPE_INVOKE,
                                      SCOPE_REGISTER_ENDPOINT))
    svc.make_endpoint("ep", n_executors=1, workers_per_executor=1, token=owner)
    fid = svc.register_function(_double, token=owner, public=True)
    bob = authority.issue("bob", (SCOPE_INVOKE,))
    out = svc.run(fid, {"x": np.ones(1)}, token=bob, sync=True, timeout=10)
    np.testing.assert_array_equal(out["y"], [2])
    svc.shutdown()


def test_endpoint_stats_shape(service):
    fid = service.register_function(_double)
    service.map(fid, [{"x": np.ones(1)}] * 5, timeout=10)
    stats = service.stats()
    assert stats["functions"] >= 1
    ep = list(stats["endpoints"].values())[0]
    assert ep["completed"] >= 5
    assert ep["queue_depth"] == 0
