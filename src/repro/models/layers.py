"""Shared model primitives: norms, RoPE, positional encodings, MLPs, embeddings.

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the param
pytree with tuples of *logical* axis names (resolved against the mesh by
``sharding.partition``). Compute follows the usual mixed-precision recipe:
bf16 weights/activations, fp32 norms/softmax/rope.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig

Params = dict
Specs = dict


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, fan_in: int, dtype) -> jnp.ndarray:
    scale = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# -- norms ---------------------------------------------------------------
def init_rmsnorm(d: int) -> Tuple[Params, Specs]:
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": ("embed",)}


def rmsnorm(x: jnp.ndarray, p: Params, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


def init_layernorm(d: int) -> Tuple[Params, Specs]:
    return (
        {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)},
        {"scale": ("embed",), "bias": ("embed",)},
    )


def layernorm(x: jnp.ndarray, p: Params, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# -- rotary / sinusoidal positions ------------------------------------------
def rope_frequencies(dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies (dim/2,); under YaRN `scaling` (a YarnConfig)
    the low frequencies are interpolated by its factor and the high ones
    kept, with a linear ramp between the dims that make beta_slow and
    beta_fast rotations over the original context (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return extra

    def correction_dim(rotations):
        return (dim * math.log(scaling.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                                  # 1: extrapolate, 0: interpolate
    return extra / scaling.factor * (1.0 - keep) + extra * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_scale(scaling) -> float:
    """The factor YaRN applies to cos and sin (1 where mscale equals
    mscale_all_dim, as in DeepSeek-V2-Lite)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, *,
               scaling=None, interleaved: bool = False) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (S,) or (..., S). Rotate-half RoPE;
    ``interleaved`` first gathers the even then the odd dims, as
    DeepSeek-V2's ``apply_rotary_pos_emb`` does, so adjacent pairs rotate
    together (the output stays in the gathered order)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta, scaling)             # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    m = rope_cos_scale(scaling)
    cos = (jnp.cos(angles) * m)[..., None, :]                # broadcast over heads
    sin = (jnp.sin(angles) * m)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    """Whisper-style fixed absolute positional embedding (n, d)."""
    half = d // 2
    log_timescale = jnp.log(10000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    scaled = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


# -- MLPs -------------------------------------------------------------------
def init_swiglu(key, d: int, f: int, dtype) -> Tuple[Params, Specs]:
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "wi": dense_init(k1, (d, f), d, dtype),
        "wg": dense_init(k2, (d, f), d, dtype),
        "wo": dense_init(k3, (f, d), f, dtype),
    }
    specs = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"), "wo": ("mlp", "embed")}
    return params, specs


def swiglu(x: jnp.ndarray, p: Params) -> jnp.ndarray:
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    g = jnp.einsum("...d,df->...f", x, p["wg"])
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, p["wo"])


def init_gelu_mlp(key, d: int, f: int, dtype) -> Tuple[Params, Specs]:
    k1, k2 = jax.random.split(key)
    params = {
        "wi": dense_init(k1, (d, f), d, dtype),
        "bi": jnp.zeros((f,), dtype),
        "wo": dense_init(k2, (f, d), f, dtype),
        "bo": jnp.zeros((d,), dtype),
    }
    specs = {"wi": ("embed", "mlp"), "bi": ("mlp",), "wo": ("mlp", "embed"), "bo": ("embed",)}
    return params, specs


def gelu_mlp(x: jnp.ndarray, p: Params) -> jnp.ndarray:
    h = jnp.einsum("...d,df->...f", x, p["wi"]) + p["bi"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, p["wo"]) + p["bo"]


# -- embeddings ---------------------------------------------------------------
def init_embedding(key, vocab: int, d: int, dtype) -> Tuple[Params, Specs]:
    tok = (jax.random.normal(key, (vocab, d), jnp.float32) * d ** -0.5).astype(dtype)
    return {"tok": tok}, {"tok": ("vocab", "embed")}


def embed(tokens: jnp.ndarray, p: Params) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def init_unembed(key, vocab: int, d: int, dtype) -> Tuple[Params, Specs]:
    w = dense_init(key, (d, vocab), d, dtype)
    return {"w": w}, {"w": ("embed", "vocab")}


def logits_from(h: jnp.ndarray, unembed_p: Optional[Params], embed_p: Params) -> jnp.ndarray:
    """fp32 logits; tied embeddings when no separate unembed is present."""
    if unembed_p is not None:
        return jnp.einsum("...d,dv->...v", h, unembed_p["w"]).astype(jnp.float32)
    return jnp.einsum("...d,vd->...v", h, embed_p["tok"]).astype(jnp.float32)


def cross_entropy_loss(
    logits: jnp.ndarray,        # (B, S, V) fp32
    targets: jnp.ndarray,       # (B, S) int32
    mask: Optional[jnp.ndarray] = None,  # (B, S) 1.0 where counted
) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return nll.mean()
    denom = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / denom
