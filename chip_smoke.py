"""Bring-up smoke run: the fabric's main path on one TPU chip at full width.

    python chip_smoke.py

Phase A sends a jitted 4096x4096 bf16 matmul plus a reduction through
FunctionService -> Forwarder -> Endpoint -> worker, cold and then warm, and
checks every result against numpy (the paper's §6 function path).

Phase B serves qwen1.5-0.5b at its published widths (24 layers, d_model
1024, vocab 151936, bf16; random weights from a seed) through
``serve_model`` on a two-endpoint service, the way
``examples/serve_models.py`` drives it: concurrent sessions with 128- and
512-token prompts stream 32 tokens each. It checks that

- the host's prefill and decode programs contain the Pallas kernels
  (``tpu_custom_call`` in the compiled text),
- the prefill's last-position logits agree with a float32 ``model.forward``
  that uses the jnp reference kernels, under the stated tolerance,
- with all 8 slots of one endpoint live at their own positions, the decode
  logits of two sessions mid-stream agree with that reference on their
  prompt and generated tokens, and their next served token is the argmax,
- every session got its tokens, and every decode step hit its session's
  resident cache (``serving.affinity_hits``).

The earlier lines of stdout are bring-up observations (set-up and compile
seconds, latencies, tokens/s, peak device memory), not benchmark cells. The
last line is one JSON object naming the device. Without a TPU, or when any
check fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import FunctionService  # noqa: E402
from repro.core.containers import ContainerSpec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.fabric import _host_for, serve_model  # noqa: E402

SEED = 0
ARCH = "qwen1.5-0.5b"
MATMUL_N = 4096
WARM_TASKS = 3
MAX_LEN = 1024
SESSIONS_PER_ENDPOINT = 8
PROMPT_LENS = (128, 512)
SESSIONS_PER_LEN = 4
NEW_TOKENS = 32

# bf16 keeps 8 mantissa bits (relative rounding 2^-9), and the error of the
# served bf16 program compounds through 24 residual layers and the 1024-wide
# tied unembedding: a few percent of relative L2 error against the float32
# forward is bf16's floor. A wrong mask, offset or layout in a kernel gives
# an error of order one.
LOGITS_REL_L2_TOL = 5e-2
# device matmul: exact bf16 products accumulated in float32; numpy sums the
# same products in float64 in another order. 1e-3 of the largest magnitude
# is far above that rounding and far below the error of a wrong result.
MATMUL_REL_TOL = 1e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(msg: str) -> None:
    print(msg, flush=True)


def _bf16_exact(rng, shape) -> np.ndarray:
    """float32 values that bf16 holds exactly, so numpy sees the device's inputs."""
    x = rng.standard_normal(shape, dtype=np.float32)
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def matmul_rowsum(doc):
    a = doc["a"].astype(jnp.bfloat16)
    b = doc["b"].astype(jnp.bfloat16)
    y = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return {"rowsum": y.sum(axis=1), "corner": y[:64, :64]}


def function_phase() -> None:
    """Phase A: a jax_jit function task through the fabric, cold then warm."""
    service = FunctionService()
    try:
        service.make_endpoint("fn-site", n_executors=1, workers_per_executor=1)
        fid = service.register_function(matmul_rowsum, name="matmul_rowsum",
                                        jax_jit=True)
        rng = np.random.default_rng(SEED)
        latencies = []
        for _ in range(1 + WARM_TASKS):
            a, b = _bf16_exact(rng, (MATMUL_N,) * 2), _bf16_exact(rng, (MATMUL_N,) * 2)
            t0 = time.monotonic()
            out = service.run(fid, {"a": a, "b": b}).result(600)
            latencies.append(time.monotonic() - t0)
            a64, b64 = a.astype(np.float64), b.astype(np.float64)
            for name, got, want in (
                ("rowsum", out["rowsum"], a64 @ b64.sum(axis=1)),
                ("corner", out["corner"], a64[:64] @ b64[:, :64]),
            ):
                err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
                scale = float(np.max(np.abs(want)))
                check(err <= MATMUL_REL_TOL * scale,
                      f"matmul {name}: max error {err} > {MATMUL_REL_TOL} x {scale}")
        snap = service.metrics.snapshot()
        compile_h = snap["histograms"].get("warming.compile_time_s", {})
        counters = snap["counters"]
        say(f"phase A: {MATMUL_N}x{MATMUL_N} bf16 matmul+rowsum, results match numpy")
        say(f"phase A: cold task {latencies[0]:.6f} s, warm tasks "
            + ", ".join(f"{t:.6f}" for t in latencies[1:]) + " s")
        say(f"phase A: warming.cold_starts={counters.get('warming.cold_starts', 0)} "
            f"warming.warm_hits={counters.get('warming.warm_hits', 0)} "
            f"warming.compile_time_s sum={compile_h.get('sum')}")
        check(counters.get("warming.cold_starts", 0) == 1, "one cold start")
        check(counters.get("warming.warm_hits", 0) == WARM_TASKS, "warm hits")
    finally:
        service.shutdown()


@contextlib.contextmanager
def jnp_reference_kernels():
    """Route the attention kernels' "auto" dispatch to the jnp reference, so
    the reference forward shares no kernel with the served program."""
    from repro.kernels.flash_attention import ops as attn_ops

    saved = attn_ops._default_impl
    attn_ops._default_impl = lambda: "ref"
    try:
        yield
    finally:
        attn_ops._default_impl = saved


def reference_logits(model: Model, params, tokens: np.ndarray) -> np.ndarray:
    """Last-position logits of a float32 forward at highest matmul precision."""
    model32 = Model(model.cfg.with_(dtype="float32"))

    def last_logits(p, toks):
        p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        h, _ = model32.forward(p, {"tokens": toks})
        return model32._logits(p, h[:, -1:])[:, 0]

    with jnp_reference_kernels(), jax.default_matmul_precision("highest"):
        out = jax.jit(last_logits)(params, jnp.asarray(tokens))
    return np.asarray(out, np.float32)


def check_logits(host, params, tokens: np.ndarray) -> None:
    logits = host._prefill(params, {"tokens": jnp.asarray(tokens)})[0]
    got = np.asarray(logits.astype(jnp.float32))
    want = reference_logits(host.model, params, tokens)
    check(got.shape == want.shape == (1, host.cfg.vocab),
          f"logits shape {got.shape} vs {want.shape}")
    check(bool(np.isfinite(got).all()), "served logits are finite")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    max_abs = float(np.max(np.abs(got - want)))
    top1 = int(np.argmax(got)) == int(np.argmax(want))
    say(f"phase B: prefill logits vs float32 reference (S={tokens.shape[1]}): "
        f"rel L2 {rel:.6e} (tol {LOGITS_REL_L2_TOL}), max abs {max_abs:.6e}, "
        f"same top-1 {top1}")
    check(rel <= LOGITS_REL_L2_TOL, f"logits rel L2 {rel} > {LOGITS_REL_L2_TOL}")


def check_decode(client, host, params, endpoint_id: str, prompts) -> None:
    """Fill every slot of one endpoint, each session `i` stepped `i + 1`
    times, then run the host's batched decode program over the live slots
    without advancing them (a step rewrites each slot's current position
    with the same values) and compare two sessions' logits with the float32
    reference on their whole history."""
    check(len(prompts) == host.n_slots, "one session per slot")
    sessions = []
    try:
        for prompt in prompts:
            sessions.append(client.session(prompt, endpoint_id=endpoint_id,
                                           timeout=600))
        for i, s in enumerate(sessions):
            for _ in range(i + 1):
                s.step(timeout=600)
        with host._lock:
            check(len(host.sessions) == host.n_slots, "every slot is live")
            logits, host.cache, _ = host._decode(
                params, jnp.asarray(host.slot_last[:, None]), host.cache,
                jnp.asarray(host.slot_pos),
            )
            slots = {s.session_id: host.sessions[s.session_id].slot
                     for s in sessions}
        logits = np.asarray(logits.astype(jnp.float32))
        # one session of each prompt length, neither in the first slot
        for s in (sessions[2], sessions[-3]):
            got = logits[slots[s.session_id]][None]
            history = np.asarray(s.history, np.int32)[None]
            want = reference_logits(host.model, params, history)
            check(bool(np.isfinite(got).all()), "decode logits are finite")
            rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            say(f"phase B: decode logits vs float32 reference (slot "
                f"{slots[s.session_id]}, position {history.shape[1] - 1}): "
                f"rel L2 {rel:.6e} (tol {LOGITS_REL_L2_TOL}), same top-1 "
                f"{int(np.argmax(got)) == int(np.argmax(want))}")
            check(rel <= LOGITS_REL_L2_TOL,
                  f"decode logits rel L2 {rel} > {LOGITS_REL_L2_TOL}")
            served = s.step(timeout=600)
            check(served == int(np.argmax(got)),
                  f"served token {served} is the decode logits' argmax")
    finally:
        for s in sessions:
            s.close(timeout=600)


def check_pallas(host, params, prompt_len: int) -> None:
    """The host's own jitted programs, compiled for this device, hold the
    Pallas kernels."""
    prefill = host._prefill.lower(
        params, {"tokens": jnp.zeros((1, prompt_len), jnp.int32)}
    ).compile().as_text()
    decode = host._decode.lower(
        params, jnp.zeros((host.n_slots, 1), jnp.int32), host.cache,
        jnp.zeros((host.n_slots,), jnp.int32),
    ).compile().as_text()
    for name, text in (("prefill", prefill), ("decode", decode)):
        n = text.count("tpu_custom_call")
        say(f"phase B: {name} program holds {n} tpu_custom_call op(s)")
        check(n > 0, f"{name} program has no Pallas kernel")


def serving_phase(cfg) -> None:
    """Phase B: fabric-served inference on a two-endpoint service."""
    t_setup = time.monotonic()
    model = Model(cfg)
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(SEED)))
    t_params = time.monotonic() - t_setup
    service = FunctionService()
    try:
        jit_spec = ContainerSpec(name="jit", capabilities={"cpu", "jit"},
                                 min_workers=0, max_workers=SESSIONS_PER_ENDPOINT)
        endpoints = [
            service.make_endpoint(f"site{i}", n_executors=1, containers=[jit_spec])
            for i in range(2)
        ]
        client = serve_model(service, model, params, name=cfg.name,
                             max_len=MAX_LEN, max_sessions=SESSIONS_PER_ENDPOINT)
        rng = np.random.default_rng(SEED + 1)

        # compile every program the window uses: per endpoint, one session
        # per prompt length (prefill per shape, slot insert, batched decode)
        t_warm = time.monotonic()
        for ep in endpoints:
            for n in PROMPT_LENS:
                with client.session(rng.integers(0, cfg.vocab, n),
                                    endpoint_id=ep.endpoint_id) as s:
                    s.step(timeout=600)
        t_warm = time.monotonic() - t_warm
        say(f"phase B: {cfg.name} {cfg.n_layers}L d_model={cfg.d_model} "
            f"vocab={cfg.vocab} {cfg.dtype}: params {t_params:.6f} s, "
            f"compile+warm-up {t_warm:.6f} s")

        before = service.metrics.snapshot()["counters"]
        prompts = [rng.integers(0, cfg.vocab, n)
                   for n in PROMPT_LENS for _ in range(SESSIONS_PER_LEN)]

        def user(prompt):
            with client.session(prompt, timeout=600) as s:
                toks = list(s.stream(NEW_TOKENS, timeout=600))
                return len(prompt), s.ttft_s, toks, s.migrations

        t0 = time.monotonic()
        with ThreadPoolExecutor(len(prompts)) as pool:
            results = [f.result() for f in [pool.submit(user, p) for p in prompts]]
        wall = time.monotonic() - t0
        after = service.metrics.snapshot()["counters"]

        total = sum(len(toks) for _, _, toks, _ in results)
        for n in PROMPT_LENS:
            ttfts = [t for m, t, _, _ in results if m == n]
            say(f"phase B: TTFT prompt {n}: "
                + ", ".join(f"{t:.6f}" for t in ttfts) + " s")
        say(f"phase B: {len(results)} sessions, {total} tokens in {wall:.6f} s "
            f"= {total / wall:.6f} tokens/s")
        for _, _, toks, migrations in results:
            check(len(toks) == NEW_TOKENS, f"session got {len(toks)} tokens")
            check(all(0 <= t < cfg.vocab for t in toks), "token ids in vocab")
            check(migrations == 0, "no session migrated")
        decode_steps = total - len(results)
        delta = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("serving.affinity_hits", "serving.cache_migrations",
                           "serving.decode_batches")}
        say(f"phase B: decode steps {decode_steps}, counters {delta}")
        check(delta["serving.affinity_hits"] == decode_steps,
              "serving.affinity_hits covers every decode step")
        check(delta["serving.cache_migrations"] == 0, "no cache migrations")
        # a compile or executable load that holds the GIL stalls heartbeats;
        # the watchdogs must take that for a stall, not for a death
        liveness = {k: after.get(k, 0) for k in (
            "endpoint.executors_lost", "endpoint.executors_readmitted",
            "forwarder.failovers")}
        say(f"phase B: liveness counters {liveness}")
        check(liveness["endpoint.executors_lost"] == 0, "no executor declared dead")
        check(liveness["forwarder.failovers"] == 0, "no task failed over")

        host = _host_for(endpoints[0].site, cfg.name)
        check_decode(client, host, params, endpoints[0].endpoint_id, prompts)
        check_pallas(host, params, PROMPT_LENS[0])
        for n in PROMPT_LENS:
            prompt = next(p for p in prompts if len(p) == n)
            check_logits(host, params, prompt[None].astype(np.int32))
    finally:
        service.shutdown()


def main() -> int:
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {backend!r}", file=sys.stderr)
        return 1
    t_start = time.monotonic()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")

    function_phase()
    cfg = get_config(ARCH)
    check(cfg.dtype == "bfloat16", f"{ARCH} serves in bf16")
    serving_phase(cfg)

    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    say(f"total seconds: {time.monotonic() - t_start:.6f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
