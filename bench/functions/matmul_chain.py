"""A few float32 matrix products on one small array, at HIGHEST precision.

The task: g = x^T x / rows, y = x g / cols, z = y g. Matrix products only,
so the answer's error is the products' precision alone. The configuration
states float32 at ``Precision.HIGHEST``; the control computes the same in
three bfloat16 passes (``Precision.HIGH`` on the TPU), the next precision
below it, written out so that it reads the same on any backend.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as gen

PRECISION = jax.lax.Precision.HIGHEST
# set from the readings in PERF.md: sound runs and the control on the chip
LIMITS = {"max_rel_err": 3e-6}


def _chain(x, dot):
    g = dot(x.T, x) / x.shape[0]
    y = dot(x, g) / x.shape[1]
    return {"z": dot(y, g)}


def _highest(a, b):
    return jnp.dot(a, b, precision=PRECISION)


def _three_pass(a, b):
    """a b from bfloat16 halves: hi hi + hi lo + lo hi, each product exact
    and summed in float32; the lo lo term is dropped."""
    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(u, v):
        return jnp.dot(u, v, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def device_fn(doc):
    return _chain(doc["x"], _highest)


def control_fn(doc):
    return _chain(doc["x"], _three_pass)


def shared(seed: int, spec: Dict) -> Dict:
    return {}


class Payloads:
    """Payload `idx` of a run, the same for the same seed."""

    def __init__(self, seed: int, spec: Dict, refs: Dict):
        self.seed, self.shape = seed, tuple(spec["x"]["shape"])

    def __call__(self, idx: int) -> Dict:
        rng = gen.rng_for(self.seed, 7, idx & 0xFFFFFFFFFFFF, int(idx < 0))
        return {"x": rng.standard_normal(self.shape, dtype=np.float32)}


def materialize(doc: Dict, shared_arrays: Dict) -> Dict:
    return doc


def reference(doc: Dict) -> Dict:
    x = np.asarray(doc["x"], np.float64)
    g = x.T @ x / x.shape[0]
    y = x @ g / x.shape[1]
    return {"z": y @ g}


def compare(got: Dict, want: Dict) -> Dict[str, float]:
    z = np.asarray(got["z"], np.float64)
    scale = float(np.max(np.abs(want["z"])))
    return {"max_rel_err": float(np.max(np.abs(z - want["z"]))) / scale}
