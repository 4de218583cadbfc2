"""Serving driver: continuous-batching LM inference behind the FaaS service.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \\
        --requests 16 --max-new-tokens 12

Requests enter as registered-function invocations (`generate`), the engine
packs them into shared-cache decode batches, and the run reports TTFT and
aggregate token throughput. On this container the reduced config runs; on a
pod the full config serves under the decode_32k sharding proven by the
dry-run.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core import FunctionService
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving.engine import ServeEngine


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_reduced(args.arch) if args.reduced else get_config(args.arch)).with_(
        dtype="float32" if args.reduced else "bfloat16"
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=args.max_batch, max_len=args.max_len)

    # the FaaS front door: a registered function that enqueues into the engine
    service = FunctionService()
    service.make_endpoint("serve-frontdoor", n_executors=1, workers_per_executor=2)

    def generate(doc):
        req = engine.submit(doc["prompt"], max_new_tokens=doc.get("max_new_tokens", 8))
        if not req.done.wait(timeout=600):
            raise TimeoutError(req.request_id)
        return {"tokens": np.asarray(req.tokens, np.int32),
                "ttft_ms": (req.first_token_at - req.submitted) * 1e3}

    fid = service.register_function(generate, name=f"generate/{cfg.name}",
                                    pass_through=True, serialize_result=False,
                                    deterministic=False)

    import threading

    stop = threading.Event()
    loop = threading.Thread(target=engine.serve_forever, args=(stop,), daemon=True)
    loop.start()

    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    futs = [
        service.run(fid, {"prompt": rng.integers(0, cfg.vocab, int(rng.integers(4, 12))),
                          "max_new_tokens": args.max_new_tokens})
        for _ in range(args.requests)
    ]
    outs = [f.result(600) for f in futs]
    stop.set()
    loop.join(timeout=5)
    wall = time.monotonic() - t0
    total = sum(len(o["tokens"]) for o in outs)
    ttfts = [o["ttft_ms"] for o in outs]
    print(f"{cfg.name}: {len(outs)} requests / {total} tokens in {wall:.2f}s "
          f"({total/wall:.1f} tok/s); TTFT mean {np.mean(ttfts):.1f}ms "
          f"p95 {np.percentile(ttfts, 95):.1f}ms")
    service.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
