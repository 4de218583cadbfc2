"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \\
        --reduced --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

--reduced trains the smoke config of the same family (what a CPU run can
hold); without it the full published config trains on the default device.
Steps run as registered FaaS functions on a local endpoint (routing +
warming + retry + telemetry), the checkpointer bounds restart loss, and the
data pipeline prefetches.
"""
from __future__ import annotations

import argparse
import json


from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core import FunctionService
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.training.optimizer import OptimizerConfig
from repro.training.train_loop import TrainConfig, Trainer


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--no-faas", action="store_true", help="run steps inline")
    ap.add_argument("--history-out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    ocfg = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps)
    tcfg = TrainConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                       ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt)

    service = None
    if not args.no_faas:
        service = FunctionService()
        service.make_endpoint("train-endpoint", n_executors=1, workers_per_executor=1)

    trainer = Trainer(model, ocfg, tcfg, service=service)
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps of {args.batch}x{args.seq} tokens", flush=True)
    history = trainer.run()
    if service is not None:
        service.shutdown()
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} over {len(history)} steps")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
    return 0 if last < first else 2


if __name__ == "__main__":
    raise SystemExit(main())
