"""Detector-frame reduction: dark subtraction, a count per frame of pixels
over a threshold, and the summed image.

Frames are uint16 and the dark frame holds whole numbers, so in float32 every
value is exact: the answer is compared exactly (limit 0). The control
computes the same in bfloat16, the precision below the stated float32.
Each task's frames are distinct (a row stamped from the task's index), so
every payload is new content for the object store and the locality caches.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from bench import traffic as gen

LIMITS = {"max_abs_err_image": 0.0, "count_mismatches": 0}
_BASE_SETS = 8


def _reduce(frames, dark, threshold, dtype):
    f = frames.astype(dtype) - dark.astype(dtype)[None]
    counts = jnp.sum(f > threshold, axis=(1, 2)).astype(jnp.int32)
    return {"counts": counts, "image": jnp.sum(f, axis=0, dtype=dtype).astype(jnp.float32)}


def device_fn(doc):
    return _reduce(doc["frames"], doc["dark"], doc["threshold"], jnp.float32)


def control_fn(doc):
    return _reduce(doc["frames"], doc["dark"], doc["threshold"], jnp.bfloat16)


def shared(seed: int, spec: Dict) -> Dict:
    h, w = spec["frames"]["shape"][1:]
    rng = gen.rng_for(seed, 8)
    return {"dark": rng.integers(90, 111, (h, w)).astype(np.float32)}


class Payloads:
    """Payload `idx` of a run: one of a few base frame sets made at set-up,
    with its first row stamped from the task's index."""

    def __init__(self, seed: int, spec: Dict, refs: Dict):
        n, h, w = spec["frames"]["shape"]
        self.threshold = float(spec["threshold"])
        self.dark = refs["dark"]
        rng = gen.rng_for(seed, 9)
        base = rng.poisson(100.0, (_BASE_SETS, n, h, w))
        hits = rng.random((_BASE_SETS, n, h, w)) < spec["hit_fraction"]
        base = np.where(hits, rng.integers(1000, 60000, base.shape), base)
        self.base = base.astype(np.uint16)
        self.seed = seed

    def __call__(self, idx: int) -> Dict:
        frames = self.base[idx % _BASE_SETS].copy()
        stamp = gen.rng_for(self.seed, 10, idx & 0xFFFFFFFFFFFF, int(idx < 0))
        frames[:, 0, :] = stamp.integers(0, 65535, frames.shape[::2], dtype=np.uint16)
        return {"frames": frames, "dark": self.dark, "threshold": self.threshold}


def materialize(doc: Dict, shared_arrays: Dict) -> Dict:
    return dict(doc, dark=shared_arrays["dark"])


def reference(doc: Dict) -> Dict:
    f = doc["frames"].astype(np.float64) - doc["dark"].astype(np.float64)[None]
    return {"counts": (f > doc["threshold"]).sum(axis=(1, 2)), "image": f.sum(axis=0)}


def compare(got: Dict, want: Dict) -> Dict[str, float]:
    image = np.asarray(got["image"], np.float64)
    counts = np.asarray(got["counts"])
    return {"max_abs_err_image": float(np.max(np.abs(image - want["image"]))),
            "count_mismatches": int(np.sum(counts != want["counts"]))}
