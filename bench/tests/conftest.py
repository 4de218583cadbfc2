"""Fixtures for the benchmark's own tests: a checkout in a temporary
directory holding a copy of ``bench/`` and tiny cells, driven on the CPU.

Run them with ``python -m pytest bench/tests`` from the repository root."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_LM = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "torch_dtype": "bfloat16",
}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_tiny_root(dst: str) -> str:
    """A checkout holding bench/ and a BENCHMARK.json whose cells are the
    repository's own, cut to a size the CPU runs in seconds."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    b = os.path.join(dst, "bench")
    lm = os.path.join(b, "configs", "qwen1.5-0.5b.json")
    with open(lm) as f:
        conf = json.load(f)
    conf.update(TINY_LM)
    write_json(lm, conf)
    p = os.path.join(b, "traffic", "chat.json")
    with open(p) as f:
        mix = json.load(f)
    mix["host"] = {"slots": 4, "max_len": 96}
    mix["rate_per_s"] = 2.0
    mix["prompt_tokens"] = {"dist": "lognormal", "median": 16, "sigma": 0.5,
                            "min": 8, "max": 32, "round_up_to": [16, 32]}
    mix["output_tokens"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 3, "max": 12}
    mix["check"] = {"requests": 2}
    mix["drain_s"] = 120
    write_json(p, mix)
    p = os.path.join(b, "traffic", "frames.json")
    with open(p) as f:
        mix = json.load(f)
    mix["payload"]["frames"]["shape"] = [2, 64, 128]
    mix["rate_per_s"] = 4.0
    mix["check"] = {"tasks": 4}
    write_json(p, mix)
    p = os.path.join(b, "traffic", "short.json")
    with open(p) as f:
        mix = json.load(f)
    mix["in_flight"] = 4
    mix["check"] = {"tasks": 0, "stride": 3}
    write_json(p, mix)
    write_json(os.path.join(dst, "BENCHMARK.json"), bm)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The program's attention kernels in Pallas interpret mode on the CPU."""
    from repro.kernels.flash_attention import ops as attn_ops

    monkeypatch.setattr(attn_ops, "_default_impl", lambda: "pallas_interpret")
