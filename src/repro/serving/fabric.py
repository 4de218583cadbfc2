"""Fabric-served inference: the serving tier meets the FaaS tiers.

The paper's DLHub case study (§7) serves ML models through the fabric; this
module makes the in-repo jax models first-class fabric workloads. Model
steps are *registered functions* carrying ``ResourceSpec(capabilities=
{"jit"})`` so routing only lands them on jit-capable container pools, and
three pieces make serving fast through the task path:

- **Session-sticky KV-cache affinity** — every task of a generation session
  carries a ``session_id``; the Forwarder's :class:`SessionRouter` pins the
  session to the endpoint holding its KV-cache slot. On endpoint death the
  binding is evicted, the next decode step lands on a survivor, and the
  :class:`ModelHost` there rebuilds the cache from the token history carried
  in the request (`serving.cache_migrations`).
- **Endpoint-level continuous batching** — concurrent decode-step tasks for
  the same model meet in a :class:`DecodeCoalescer` (the interchange tier's
  ``BatchCoalescer`` generalized from task frames to kernel batches): the
  first arrival leads, waits a bounded window for peers, and runs ONE
  batched ``decode_step`` over the shared stacked cache; followers just
  collect their token.
- **cache_bytes admission control** — a host's slot count derives from
  :func:`repro.serving.kv_cache.cache_bytes`; prefill beyond it raises
  :class:`CacheAdmissionError` instead of silently growing decode state.

Hosts are *site state*: the serving functions are registered once and
``site_aware`` metadata hands them the executing endpoint's
:class:`~repro.core.worker.SiteRuntime`, where each endpoint lazily builds
its own :class:`ModelHost` (params shared in-process; a real deployment
loads per site). See docs/serving.md.
"""
from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.containers import ResourceSpec
from ..core.metrics import MetricsRegistry
from ..kernels.flash_attention.ops import decode_block_k
from ..models.model import Model
from . import kv_cache

# Families whose decode state is positionally idempotent: re-running a step
# for a slot at an unchanged position rewrites the same K/V rows with the
# same values, so slots *absent* from a merged kernel invocation are
# unharmed. Recurrent state (ssm/hybrid) accumulates per step and would be
# corrupted, so those families serve unbatched (per-session caches).
_BATCHABLE_FAMILIES = ("dense", "moe")


class CacheAdmissionError(RuntimeError):
    """No free KV-cache slot under the host's ``cache_bytes`` budget."""


# ---------------------------------------------------------------------------
# decode coalescer
# ---------------------------------------------------------------------------
class _PendingDecode:
    __slots__ = ("token", "error", "event")

    def __init__(self):
        self.token: Optional[int] = None
        self.error: Optional[BaseException] = None
        self.event = threading.Event()


class DecodeCoalescer:
    """Merge concurrent decode-step calls into one batched kernel invocation.

    The interchange tier's ``BatchCoalescer`` generalized to kernel batches:
    instead of a pump thread flushing task frames on size/deadline, the
    *callers themselves* combine — the first arrival becomes the leader,
    waits up to ``window_s`` for more slots to join (stopping early once
    every currently-active session has arrived), then runs ``step_fn`` over
    the merged slot set while followers block on their own result. Exactly
    one kernel invocation serves the whole batch.

    Each batch run is counted in `metrics`: ``serving.decode_batches``, its
    size in ``serving.merged_per_step``, and why the leader stopped waiting
    in ``serving.window_full`` (every active session arrived) or
    ``serving.window_expired`` (the window ran out).
    """

    def __init__(
        self,
        step_fn: Callable[[List[int]], Dict[int, int]],
        window_s: float = 0.003,
        target_fn: Optional[Callable[[], int]] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        self._step = step_fn
        self.window_s = window_s
        self._target = target_fn or (lambda: 1)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._cond = threading.Condition()
        self._waiting: Dict[int, _PendingDecode] = {}
        self._leading = False

    def submit(self, slot: int) -> int:
        mine = _PendingDecode()
        with self._cond:
            self._waiting[slot] = mine
            self._cond.notify_all()
            # follower path: somebody is already leading — wait for them to
            # take (and serve) our slot, or for leadership to free up
            if self._leading:
                with self.metrics.span("serving.coalesce_follow"):
                    while self._leading and not mine.event.is_set():
                        self._cond.wait(timeout=self.window_s)
            if mine.event.is_set():
                return self._collect(mine)
            self._leading = True
        try:
            deadline = time.monotonic() + self.window_s
            with self._cond, self.metrics.span("serving.coalesce_lead"):
                while (
                    len(self._waiting) < max(1, self._target())
                    and (remaining := deadline - time.monotonic()) > 0
                ):
                    self._cond.wait(timeout=remaining)
                full = len(self._waiting) >= max(1, self._target())
                batch = dict(self._waiting)
                self._waiting.clear()
            try:
                tokens = self._step(sorted(batch))
            except BaseException as exc:  # noqa: BLE001 — fan out, don't hang peers
                with self._cond:
                    for pending in batch.values():
                        pending.error = exc
                        pending.event.set()
                    self._cond.notify_all()
                raise
            self.metrics.counter("serving.decode_batches").inc()
            self.metrics.histogram("serving.merged_per_step").observe(len(batch))
            self.metrics.counter(
                "serving.window_full" if full else "serving.window_expired"
            ).inc()
            with self._cond:
                for s, pending in batch.items():
                    pending.token = tokens[s]
                    pending.event.set()
                self._cond.notify_all()
        finally:
            with self._cond:
                self._leading = False
                self._cond.notify_all()
        return self._collect(mine)

    @staticmethod
    def _collect(pending: _PendingDecode) -> int:
        if pending.error is not None:
            raise pending.error
        assert pending.token is not None
        return pending.token


# ---------------------------------------------------------------------------
# per-endpoint model host
# ---------------------------------------------------------------------------
@dataclass
class _SessionState:
    slot: int
    pos: int                      # next cache write position
    last: int                     # last accepted token (decode input)
    cache: Any = None             # unbatched mode: private batch-1 cache
    touched: float = field(default_factory=time.monotonic)


class ModelHost:
    """One endpoint's serving state for one model: params, slotted KV cache,
    session table, and the decode coalescer.

    ``batching=True`` (attention families) keeps ONE stacked cache of
    ``n_slots`` sequences — prefills insert into free slots, concurrent
    decode steps coalesce into one batched kernel. Other families (or
    ``batching=False``, the per-request baseline) give each session a
    private batch-1 cache and run one kernel per request, serialized like
    independent device programs.
    """

    def __init__(
        self,
        model: Model,
        params,
        max_len: int = 96,
        max_sessions: int = 8,
        cache_bytes_budget: Optional[int] = None,
        batching: bool = True,
        window_s: float = 0.003,
        metrics=None,
    ):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.max_len = max_len
        if batching and self.cfg.family not in _BATCHABLE_FAMILIES:
            batching = False
        self.batching = batching
        # admission control: slots the cache_bytes budget affords
        per_seq = kv_cache.cache_bytes(self.cfg, 1, max_len)
        if cache_bytes_budget is not None:
            max_sessions = max(1, min(max_sessions, cache_bytes_budget // per_seq))
        self.n_slots = int(max_sessions)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.metrics.gauge("serving.cache_bytes").set(
            kv_cache.cache_bytes(self.cfg, self.n_slots, max_len)
        )

        # the served programs also return the step's routing counts, read
        # back with its tokens in one transfer (_pick); named as the model's
        # own methods, which the device trace's program names follow
        def prefill(params, batch):
            return model.prefill(params, batch, with_stats=True)

        def decode_step(params, token, cache, pos):
            return model.decode_step(params, token, cache, pos, with_stats=True)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._pick = jax.jit(
            lambda logits, counts: jnp.concatenate(
                [jnp.argmax(logits, axis=-1).astype(jnp.int32), counts])
        )
        self._insert = jax.jit(kv_cache.insert_sequence, donate_argnums=(0,))
        self._routed = self.cfg.moe is not None

        self._lock = threading.Lock()
        self.sessions: Dict[str, _SessionState] = {}
        self._free = set(range(self.n_slots))
        if batching:
            self.cache, _ = model.init_cache(self.n_slots, max_len)
            self.slot_pos = np.zeros(self.n_slots, np.int32)
            # the decode kernel's K/V block over this cache (MLA attends
            # over one latent head of kv_lora + rope)
            if self.cfg.mla is not None:
                kv, width = 1, self.cfg.mla.kv_lora_rank + self.cfg.mla.qk_rope_dim
            else:
                kv, width = self.cfg.n_kv_heads, self.cfg.hd
            self.block_k = decode_block_k(max_len, kv, width)
            self.slot_last = np.zeros(self.n_slots, np.int32)
            self.coalescer = DecodeCoalescer(
                self._batched_step,
                window_s=window_s,
                target_fn=lambda: len(self.sessions),
                metrics=self.metrics,
            )
        else:
            # each session decodes in a private batch-1 cache of max_len
            # positions; the prompt-length cache prefill returns goes into
            # a fresh one so decode writes land inside the cache
            self._to_decode_cache = jax.jit(
                lambda c: kv_cache.insert_sequence(
                    model.init_cache(1, max_len)[0], c, 0
                )
            )
            self.coalescer = None

    # -- metrics helpers ---------------------------------------------------
    def _count(self, name: str, n: int = 1) -> None:
        self.metrics.counter(name).inc(n)

    def _count_routing(self, phase: str, stats, rows: List[int]) -> None:
        """A step's routing counts (``Model.decode_step``'s `with_stats`),
        read back with its tokens. Over the batch `rows` the step served:
        ``serving.moe_assign_held.<phase>`` (token, choice) pairs routed to
        the experts held here, ``serving.moe_assign_all.<phase>`` all pairs;
        ``serving.moe_rows.<phase>`` the expert rows computed, idle slots'
        and padding included."""
        if self._routed:
            self._count(f"serving.moe_assign_held.{phase}", int(stats[2:][rows].sum()))
            self._count(f"serving.moe_assign_all.{phase}", int(stats[1]) * len(rows))
            self._count(f"serving.moe_rows.{phase}", int(stats[0]))

    # -- session lifecycle -------------------------------------------------
    def prefill(self, session: str, tokens) -> int:
        """Open (or rebuild) `session` from its full token history; returns
        the next predicted token. Raises CacheAdmissionError when every slot
        under the cache_bytes budget is taken."""
        tokens = np.asarray(tokens, np.int32)
        if len(tokens) >= self.max_len:
            raise ValueError(
                f"session {session}: {len(tokens)} tokens >= max_len {self.max_len}"
            )
        with self._lock:
            old = self.sessions.pop(session, None)
            if old is not None:
                self._free.add(old.slot)
            if not self._free:
                self._count("serving.admission_rejects")
                raise CacheAdmissionError(
                    f"model host full: {self.n_slots} KV slots "
                    f"({kv_cache.cache_bytes(self.cfg, self.n_slots, self.max_len)} "
                    f"bytes) all serving sessions"
                )
            slot = self._free.pop()
        batch = {"tokens": tokens[None, :]}
        if self.cfg.family == "encdec":
            batch["frames"] = np.zeros(
                (1, self.cfg.enc_seq, self.cfg.d_model), np.float32
            )
        with self.metrics.span("serving.prefill"):
            logits, seq_cache, counts = self._prefill(self.params, batch)
            out = np.asarray(self._pick(logits, counts))
            first = int(out[0])
        self._count_routing("prefill", out[1:], [0])
        if not self.batching:
            seq_cache = self._to_decode_cache(seq_cache)
        with self._lock:
            if self.batching:
                with self.metrics.span("serving.insert"):
                    self.cache = self._insert(self.cache, seq_cache, slot)
                self.slot_pos[slot] = len(tokens)
                self.slot_last[slot] = first
                seq_cache = None
            self.sessions[session] = _SessionState(
                slot=slot, pos=len(tokens), last=first, cache=seq_cache
            )
            n_active = len(self.sessions)
        self._count("serving.prefills")
        self._count("serving.tokens_generated")
        self.metrics.gauge("serving.sessions_active").set(n_active)
        return first

    def decode(self, session: str, tokens) -> Tuple[int, bool]:
        """One decode step for `session`; returns ``(next_token, migrated)``.

        A hit (`serving.affinity_hits`) runs against the resident cache slot;
        a miss means the session's home died and sticky routing moved it here
        — the cache is rebuilt from the full token history (`tokens`), which
        is the explicit re-prefill migration path.
        """
        with self._lock:
            st = self.sessions.get(session)
        if st is None:
            self._count("serving.cache_migrations")
            return self.prefill(session, tokens), True
        self._count("serving.affinity_hits")
        if self.batching:
            nxt = self.coalescer.submit(st.slot)
        else:
            with self._lock:  # per-request baseline: one kernel per request
                tok = jnp.asarray([[st.last]], jnp.int32)
                pos = jnp.asarray([st.pos], jnp.int32)
                logits, st.cache, counts = self._decode(self.params, tok, st.cache, pos)
                out = np.asarray(self._pick(logits, counts))
                nxt = int(out[0])
                self._count_routing("decode", out[1:], [0])
                st.pos += 1
        with self._lock:
            st.last = nxt
            st.touched = time.monotonic()
        self._count("serving.tokens_generated")
        return nxt, False

    def release(self, session: str) -> bool:
        with self._lock:
            st = self.sessions.pop(session, None)
            if st is not None:
                self._free.add(st.slot)
            n_active = len(self.sessions)
        self.metrics.gauge("serving.sessions_active").set(n_active)
        return st is not None

    # -- batched decode kernel --------------------------------------------
    def _batched_step(self, slots: List[int]) -> Dict[int, int]:
        """One decode kernel over the shared stacked cache serving `slots`.

        Every slot's row advances at its own position (vector pos); slots
        not in `slots` rewrite their current position with their last token
        — byte-identical values their own next step overwrites again, which
        is why batching is gated to attention families.

        Per step, ``serving.kv_blocks_read`` counts the K/V blocks the decode
        kernel reads per layer (each slot's, up to its position) and
        ``serving.kv_blocks_cached`` the blocks the cache holds; over MLA's
        latent cache ``serving.mla_positions_read.decode`` counts the
        positions it reads per layer (each slot's position + 1). The routing
        counts come back with the tokens (`_count_routing`).
        """
        with self._lock, self.metrics.span("serving.step"):
            bk = self.block_k
            self._count("serving.kv_blocks_read", int(((self.slot_pos + bk) // bk).sum()))
            self._count("serving.kv_blocks_cached", self.n_slots * -(-self.max_len // bk))
            if self.cfg.mla is not None:
                self._count("serving.mla_positions_read.decode", int((self.slot_pos + 1).sum()))
            tok = self.slot_last[:, None].copy()
            pos_vec = jnp.asarray(self.slot_pos)
            logits, self.cache, counts = self._decode(
                self.params, jnp.asarray(tok), self.cache, pos_vec
            )
            with self.metrics.span("serving.readback"):
                packed = np.asarray(self._pick(logits, counts))
            nt = packed[: self.n_slots]
            self._count_routing("decode", packed[self.n_slots:], slots)
            out = {}
            for s in slots:
                self.slot_last[s] = int(nt[s])
                self.slot_pos[s] += 1
                out[s] = int(nt[s])
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "batching": self.batching,
                "slots": self.n_slots,
                "active": len(self.sessions),
                "free": len(self._free),
                "decode_batches": self.metrics.counter("serving.decode_batches").value,
                "merged": int(self.metrics.histogram("serving.merged_per_step").sum),
                "cache": kv_cache.summarize(self.cfg, self.n_slots, self.max_len),
            }


# ---------------------------------------------------------------------------
# registration: model specs + per-site hosts
# ---------------------------------------------------------------------------
@dataclass
class ModelServeSpec:
    """Blueprint a site builds its ModelHost from (in-process the params are
    shared; a real deployment loads them per endpoint)."""

    name: str
    model: Model
    params: Any
    max_len: int
    max_sessions: int
    cache_bytes_budget: Optional[int]
    batching: bool
    window_s: float


_SPECS: Dict[str, ModelServeSpec] = {}
_SPECS_LOCK = threading.Lock()


def _host_for(site, name: str) -> ModelHost:
    with _SPECS_LOCK:
        spec = _SPECS.get(name)
    if spec is None:
        raise KeyError(f"model {name!r} not served (serve_model first)")

    def build() -> ModelHost:
        return ModelHost(
            spec.model,
            spec.params,
            max_len=spec.max_len,
            max_sessions=spec.max_sessions,
            cache_bytes_budget=spec.cache_bytes_budget,
            batching=spec.batching,
            window_s=spec.window_s,
            metrics=site.metrics,
        )

    return site.get_or_create(("serving-host", name), build)


def reset_serving() -> None:
    """Drop every served-model spec (tests/benchmarks hygiene; hosts live in
    their endpoints' SiteRuntimes and die with them)."""
    with _SPECS_LOCK:
        _SPECS.clear()


# the three serving functions: module-level so registration is idempotent
# (same content hash) no matter how many models/services register them
def _serve_prefill(doc, site):
    host = _host_for(site, doc["model"])
    token = host.prefill(doc["session"], doc["tokens"])
    return {"token": token, "endpoint": site.endpoint_id, "migrated": False}


def _serve_decode(doc, site):
    host = _host_for(site, doc["model"])
    token, migrated = host.decode(doc["session"], doc["tokens"])
    return {"token": token, "endpoint": site.endpoint_id, "migrated": migrated}


def _serve_release(doc, site):
    host = _host_for(site, doc["model"])
    return host.release(doc["session"])


def serve_model(
    service,
    model: Model,
    params,
    name: str,
    max_len: int = 96,
    max_sessions: int = 8,
    cache_bytes_budget: Optional[int] = None,
    batching: bool = True,
    window_s: float = 0.003,
    token=None,
) -> "ServingClient":
    """Register `model` as a fabric-served inference workload.

    Registers prefill/decode/release as public fabric functions requiring
    the ``jit`` capability and records the host blueprint every jit-capable
    endpoint builds lazily on first task. Returns a :class:`ServingClient`
    bound to this service.
    """
    spec = ModelServeSpec(
        name=name,
        model=model,
        params=params,
        max_len=max_len,
        max_sessions=max_sessions,
        cache_bytes_budget=cache_bytes_budget,
        batching=batching,
        window_s=window_s,
    )
    with _SPECS_LOCK:
        _SPECS[name] = spec
    requirements = ResourceSpec(capabilities=frozenset({"jit"}))
    common = dict(
        public=True, requirements=requirements, token=token,
        site_aware=True, serialize_result=False,
    )
    fids = {
        "prefill": service.register_function(
            _serve_prefill, name="serving/prefill",
            description="prefill-into-slot for served models",
            **common,
        ),
        "decode": service.register_function(
            _serve_decode, name="serving/decode_step",
            description="coalesced decode step for served models", **common,
        ),
        "release": service.register_function(
            _serve_release, name="serving/release",
            description="free a session's KV-cache slot", **common,
        ),
    }
    return ServingClient(service, name, fids, max_len=max_len, token=token)


# ---------------------------------------------------------------------------
# client surface
# ---------------------------------------------------------------------------
class ServeSession:
    """One sticky generation session: every step routes with the same
    ``session_id`` so the Forwarder pins it to the endpoint holding its
    KV-cache slot."""

    def __init__(self, client: "ServingClient", session_id: str,
                 history: List[int], first_token: int, endpoint: str,
                 ttft_s: float):
        self._client = client
        self.session_id = session_id
        self.history = history          # prompt + every generated token
        self.tokens = [first_token]     # generated tokens only
        self.endpoints = [endpoint]     # serving endpoint per step
        self.migrations = 0
        self.ttft_s = ttft_s
        self.closed = False

    def step(self, timeout: float = 60.0) -> int:
        """One decode step (one fabric task). The full token history rides
        along so a failed-over session can re-prefill on its new endpoint."""
        out = self._client._call(
            "decode",
            {"session": self.session_id, "tokens": list(self.history)},
            session_id=self.session_id,
            timeout=timeout,
        )
        self.history.append(out["token"])
        self.tokens.append(out["token"])
        self.endpoints.append(out["endpoint"])
        self.migrations += bool(out["migrated"])
        return out["token"]

    def stream(self, max_new_tokens: int, eos_id: int = -1,
               timeout: float = 60.0) -> Iterator[int]:
        """Yield generated tokens (including the prefill's first token)
        until `max_new_tokens`, EOS, or the host's context limit."""
        yield self.tokens[0]
        while (
            len(self.tokens) < max_new_tokens
            and self.tokens[-1] != eos_id
            and len(self.history) < self._client.max_len - 1
        ):
            yield self.step(timeout=timeout)

    def close(self, timeout: float = 30.0) -> None:
        if self.closed:
            return
        self.closed = True
        try:
            self._client._call(
                "release", {"session": self.session_id},
                session_id=self.session_id, timeout=timeout,
            )
        finally:
            sessions = getattr(self._client.service.forwarder, "sessions", None)
            if sessions is not None:
                sessions.forget(self.session_id)

    def __enter__(self) -> "ServeSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ServingClient:
    """Client surface over a served model: open sticky sessions, stream
    tokens, observe TTFT through the fabric metrics."""

    def __init__(self, service, model_name: str, fids: Dict[str, str],
                 max_len: int, token=None):
        self.service = service
        self.model_name = model_name
        self.fids = fids
        self.max_len = max_len
        self.token = token

    def _call(self, which: str, doc: dict, session_id: Optional[str] = None,
              endpoint_id: Optional[str] = None, timeout: float = 60.0):
        doc = {"model": self.model_name, **doc}
        future = self.service.run(
            self.fids[which], doc,
            endpoint_id=endpoint_id, session_id=session_id,
            token=self.token,
        )
        return future.result(timeout)

    def session(self, prompt, session_id: Optional[str] = None,
                endpoint_id: Optional[str] = None, timeout: float = 60.0,
                admission_retries: int = 2) -> ServeSession:
        """Prefill `prompt` into a slot somewhere and return the sticky
        session. A CacheAdmissionError (endpoint full under its cache_bytes
        budget) forgets the binding and retries, letting the policy place
        the session on an endpoint with free slots."""
        session_id = session_id or f"s-{uuid.uuid4().hex[:12]}"
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        t0 = time.monotonic()
        attempt = 0
        while True:
            try:
                out = self._call(
                    "prefill", {"session": session_id, "tokens": prompt},
                    session_id=session_id, endpoint_id=endpoint_id,
                    timeout=timeout,
                )
                break
            except CacheAdmissionError:
                attempt += 1
                sessions = getattr(self.service.forwarder, "sessions", None)
                if sessions is not None:
                    sessions.forget(session_id)
                if attempt > admission_retries:
                    raise
        ttft = time.monotonic() - t0
        self.service.metrics.histogram("serving.ttft_s").observe(ttft)
        return ServeSession(
            self, session_id, history=prompt + [out["token"]],
            first_token=out["token"], endpoint=out["endpoint"], ttft_s=ttft,
        )

    def generate(self, prompt, max_new_tokens: int = 16, eos_id: int = -1,
                 timeout: float = 60.0) -> List[int]:
        with self.session(prompt, timeout=timeout) as s:
            return list(s.stream(max_new_tokens, eos_id=eos_id, timeout=timeout))
