"""Driver for a language model served through the fabric (``kind: serve_lm``).

Deployment: one ``FunctionService`` with one jit-capable endpoint, standing
for one chip, and ``serve_model`` on it with the mix's slot count and
``max_len``. The window drives ``ServingClient.session`` and
``ServeSession.step``: one thread per request, an open loop of arrivals. The
check runs the plain reference over a sample of the finished requests.
"""
from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import traffic as gen
from bench.harness import Run, TaskRec, percentile, record_futures
from bench.reference import dense_lm

_FAMILY = {"qwen2": "dense"}
# the Qwen2 layout carries q/k/v biases
_QKV_BIAS = {"qwen2": True}


def model_config(conf: Dict):
    """The program's ModelConfig for the published keys in `conf`."""
    from repro.configs.base import ModelConfig

    mt = conf["model_type"]
    return ModelConfig(
        name=conf["name"],
        family=_FAMILY[mt],
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        head_dim=conf.get("head_dim", 0),
        qkv_bias=_QKV_BIAS[mt],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"],
    )


@dataclass
class ReqRec:
    idx: int
    due: float
    prompt_len: int
    out_len: int
    times: List[float] = field(default_factory=list)   # each token received
    history: Optional[List[int]] = None
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.history is not None and self.error is None


class Driver:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell = cell
        self.conf = cell.config
        self.mix = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.slots = int(self.mix["host"]["slots"])
        self.max_len = int(self.mix["host"]["max_len"])
        self.tasks: List[TaskRec] = []
        self.plan(self.mix)

    def plan(self, mix: Dict, seed: Optional[int] = None) -> None:
        """The requests of the next window (a sweep re-plans at other rates)."""
        seed = self.seed if seed is None else seed
        self.requests = gen.sessions(mix, seed, self.seconds)
        self.recs: List[ReqRec] = []
        self.lateness: List[float] = []
        self.prompts = {r.idx: gen.token_ids(seed, r.idx, r.prompt_len,
                                             self.conf["vocab_size"]) for r in self.requests}

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.core import FunctionService
        from repro.core.containers import ContainerSpec
        from repro.models.model import Model
        from repro.serving.fabric import serve_model

        conf = self.conf
        self.cfg = model_config(conf)
        self.params = dense_lm.init_params(self.seed, conf, dtype=self.cfg.dtype)
        model = Model(self.cfg)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if want != got:
            raise ValueError(f"weights' layout differs from the program's: {got} vs {want}")
        vocab = conf["vocab_size"]

        self.service = FunctionService()
        jit = ContainerSpec(name="jit", capabilities={"cpu", "jit"},
                            min_workers=0, max_workers=self.slots)
        self.endpoint = self.service.make_endpoint("chip0", n_executors=1, containers=[jit])
        dep = conf.get("deployment", {}).get("serve_model", {})
        self.client = serve_model(
            self.service, model, self.params, name=self.cfg.name,
            max_len=self.max_len, max_sessions=self.slots,
            batching=dep.get("batching", True), window_s=dep.get("window_s", 0.003),
        )
        kinds = {fid: which for which, fid in self.client.fids.items()}

        def meta_of(kind, doc):
            return {"n_tokens": len(doc.get("tokens", ()))}

        record_futures(self.service, kinds, meta_of, self.tasks)
        # every prompt length the window sends: prefill and slot insert per
        # length, the batched decode, the host's small programs
        for n in sorted({r.prompt_len for r in self.requests}):
            prompt = gen.token_ids(self.seed, 10**6 + n, n, vocab)
            with self.client.session(prompt, timeout=1200) as s:
                s.step(timeout=1200)

    # ------------------------------------------------------------ window
    def _serve(self, r, due: float, rec: ReqRec, deadline: float) -> None:
        try:
            s = self.client.session(self.prompts[r.idx],
                                    timeout=max(1.0, deadline - time.monotonic()))
            rec.times.append(time.monotonic())
            for _ in range(r.out_len - 1):
                s.step(timeout=max(1.0, deadline - time.monotonic()))
                rec.times.append(time.monotonic())
            rec.history = list(s.history)
            s.close(timeout=max(1.0, deadline - time.monotonic()))
        except Exception as exc:  # noqa: BLE001 — counted as failed
            rec.error = f"{type(exc).__name__}: {exc}"

    def window(self, run: Run) -> None:
        drain = float(self.mix.get("drain_s", 60))
        pool = ThreadPoolExecutor(max_workers=2 * self.slots + 8,
                                  thread_name_prefix="bench-user")
        t0 = time.monotonic()
        deadline = t0 + self.seconds + drain
        run.window = (t0, t0 + self.seconds)
        self.window_end = t0 + self.seconds
        futs = []
        for r in self.requests:
            due = t0 + r.due
            time.sleep(max(0.0, due - time.monotonic()))
            self.lateness.append(time.monotonic() - due)
            rec = ReqRec(r.idx, due, r.prompt_len, r.out_len)
            self.recs.append(rec)
            futs.append(pool.submit(self._serve, r, due, rec, deadline))
        time.sleep(max(0.0, t0 + self.seconds - time.monotonic()))
        for f in futs:
            try:
                f.result(timeout=max(0.0, deadline + 5 - time.monotonic()))
            except Exception:  # noqa: BLE001 — the record says what failed
                pass
        self.drained_at = time.monotonic()
        pool.shutdown(wait=False, cancel_futures=True)

    # ------------------------------------------------------------ results
    def attempted(self) -> int:
        return len(self.recs)

    def failed(self) -> int:
        return sum(not r.done for r in self.recs)

    def end_to_end(self) -> Dict[str, float]:
        done = [r for r in self.recs if r.done]
        ttft = [r.times[0] - r.due for r in done]
        itl = [b - a for r in done for a, b in zip(r.times, r.times[1:])]
        out = {}
        if ttft:
            out["ttft_p95_s"] = percentile(ttft, 95)
            out["ttft_p50_s"] = percentile(ttft, 50)
        if itl:
            out["itl_p95_s"] = percentile(itl, 95)
            out["itl_p50_s"] = percentile(itl, 50)
        out["requests_done"] = len(done)
        out["tokens_done"] = sum(len(r.times) for r in done)
        return out

    def free(self) -> None:
        """Shut the fabric down and drop the host's KV cache."""
        from repro.serving.fabric import _host_for, reset_serving

        try:
            host = _host_for(self.endpoint.site, self.cfg.name)
            host.cache = None
            host.params = None
        except Exception:  # noqa: BLE001 — no host was built
            pass
        self.service.shutdown()
        reset_serving()
        gc.collect()

    def _sample(self) -> List[ReqRec]:
        """The requests the check compares: drawn from the seed among the
        finished ones, the longest always among them."""
        done = [r for r in self.recs if r.done]
        if not done:
            return []
        longest = max(done, key=lambda r: (r.out_len, r.prompt_len)).idx
        k = int(self.mix["check"]["requests"])
        picked = set(gen.sample_indices(self.seed, [r.idx for r in done], k, must=longest))
        return [r for r in done if r.idx in picked]

    def _compare(self, gaps_of) -> Dict:
        """Widest gap, over a sample of finished requests with the longest in
        it, between the float32 reference's best logit and its logit of the
        token that `gaps_of` reads at each served position."""
        limit = self.conf["check"]["max_logit_gap"]
        widest, tokens = 0.0, 0
        sample = self._sample()
        for r in sample:
            gaps = gaps_of(self.params, self.conf, r.history, r.prompt_len, pad_to=self.max_len)
            tokens += len(gaps)
            widest = max(widest, float(np.max(gaps)))
        return {"correct": bool(tokens > 0 and widest <= limit),
                "numbers": [("max_logit_gap", widest, limit)],
                "checked": {"requests": len(sample), "tokens": tokens}}

    def check(self) -> Dict:
        """The program's served tokens against the reference."""
        return self._compare(dense_lm.served_gaps)

    def control_check(self) -> Dict:
        """The same comparison with the control in the program's place: the
        float8 reference, read for the token it puts first at each position."""
        return self._compare(dense_lm.control_gaps)

    def occupancy(self) -> Dict[str, int]:
        """Peak sessions holding a slot and peak KV positions they filled,
        read every 0.25 s from the requests' own token times."""
        spans = [(r.times[0], r.times[-1], r.prompt_len, r.times) for r in self.recs if r.times]
        peak = {"sessions": 0, "positions": 0}
        if not spans:
            return peak
        t, end = min(a for a, *_ in spans), max(b for _, b, *_ in spans)
        while t <= end:
            live = [(n, ts) for a, b, n, ts in spans if a <= t <= b]
            peak["sessions"] = max(peak["sessions"], len(live))
            peak["positions"] = max(peak["positions"],
                                    sum(n + int(np.searchsorted(ts, t, "right")) for n, ts in live))
            t += 0.25
        return peak

    def gap_notes(self) -> List[str]:
        """Where the gaps between tokens lie: quantiles of all of them, and
        how many hold another request's prefill (its first token received
        inside the gap)."""
        done = [r for r in self.recs if r.done]
        firsts = np.sort([r.times[0] for r in done])
        gaps, held = [], []
        for r in done:
            for a, b in zip(r.times, r.times[1:]):
                gaps.append(b - a)
                held.append(np.searchsorted(firsts, b, "right") > np.searchsorted(firsts, a, "right"))
        if not gaps:
            return []
        gaps, held = np.asarray(gaps), np.asarray(held)
        qs = (50, 80, 90, 93, 94, 95, 96, 97, 99, 100)
        plain = gaps[~held] if (~held).any() else gaps
        return ["itl quantiles " + " ".join(f"p{q}={np.percentile(gaps, q):.6f}" for q in qs)
                + f" of {gaps.size} gaps",
                f"itl gaps holding a prefill: {int(held.sum())}, median "
                f"{np.median(gaps[held]) if held.any() else 0:.6f} s; the others' p95 "
                f"{np.percentile(plain, 95):.6f} s, p99 {np.percentile(plain, 99):.6f} s"]

    def notes(self) -> List[str]:
        late = self.lateness or [0.0]
        occ = self.occupancy()
        held = self.slots * self.max_len
        return self.gap_notes() + [f"generator lateness: median {np.median(late):.6f} s, "
                f"max {np.max(late):.6f} s over {len(late)} arrivals",
                f"drain: {self.drained_at - self.window_end:.3f} s after the window closed",
                f"sessions in flight: peak {occ['sessions']} of {self.slots} slots; KV "
                f"positions filled: peak {occ['positions']} of {held} "
                f"({100.0 * occ['positions'] / held:.1f}%)"]
