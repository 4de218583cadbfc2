"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

Prefill expands the compressed latent into per-head k/v; decode runs the
*absorbed* form: queries are projected into latent space and attention runs
as MQA with a single (kv_lora + rope)-wide kv head — the cache stores one
latent row [c_kv, k_rope] per token, the technique's memory advantage, and
the decode kernel reads it once as the key and its first kv_lora columns
as the value.

Queries come from a low-rank step (wdq, q_norm, wuq) or, where the config's
``q_lora_rank`` is None (DeepSeek-V2-Lite), from one direct projection wq.
RoPE follows the config's YaRN scaling and pair order; the softmax scale is
qk**-0.5 times YaRN's mscale(mscale_all_dim) squared.
"""
from __future__ import annotations

from typing import Optional


import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.flash_attention import ops as attn_ops
from ..sharding import partition
from . import layers


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    dt = layers.dtype_of(cfg)
    ks = jax.random.split(key, 7)
    if m.q_lora_rank is None:
        params = {"wq": layers.dense_init(ks[0], (D, H, qk), D, dt)}
        specs = {"wq": ("embed", "heads", None)}
    else:
        params = {
            "wdq": layers.dense_init(ks[0], (D, m.q_lora_rank), D, dt),
            "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
            "wuq": layers.dense_init(ks[1], (m.q_lora_rank, H, qk), m.q_lora_rank, dt),
        }
        specs = {"wdq": ("embed", "latent"), "q_norm": (None,), "wuq": ("latent", "heads", None)}
    params |= {
        "wdkv": layers.dense_init(ks[2], (D, m.kv_lora_rank), D, dt),
        "wkr": layers.dense_init(ks[3], (D, m.qk_rope_dim), D, dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wuk": layers.dense_init(ks[4], (m.kv_lora_rank, H, m.qk_nope_dim), m.kv_lora_rank, dt),
        "wuv": layers.dense_init(ks[5], (m.kv_lora_rank, H, m.v_head_dim), m.kv_lora_rank, dt),
        "wo": layers.dense_init(ks[6], (H, m.v_head_dim, D), H * m.v_head_dim, dt),
    }
    specs |= {
        "wdkv": ("embed", "latent"),
        "wkr": ("embed", None),
        "kv_norm": (None,),
        "wuk": ("latent", "heads", None),
        "wuv": ("latent", "heads", None),
        "wo": ("heads", None, "embed"),
    }
    return params, specs


def _norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def softmax_scale(cfg: ModelConfig) -> float:
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= layers.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg):
    return layers.apply_rope(x, positions, cfg.rope_theta, scaling=cfg.rope_scaling,
                             interleaved=cfg.mla.rope_interleaved)


def _queries(p, x, cfg, positions):
    m = cfg.mla
    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    else:
        ql = _norm(jnp.einsum("bsd,dr->bsr", x, p["wdq"]), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", ql, p["wuq"])
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    if positions is not None:
        q_rope = _rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _latent_kv(p, x, cfg, positions):
    c_kv = _norm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), p["kv_norm"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["wkr"])
    if positions is not None:
        k_rope = _rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0]
    return c_kv, k_rope


def mla_attention(
    p,
    x: jnp.ndarray,                        # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    return_cache: bool = False,
):
    """Prefill/train path: expand latent to per-head k/v, causal attention."""
    m = cfg.mla
    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latent_kv(p, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuv"])
    H = cfg.n_heads
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (*k_rope.shape[:2], H, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_seq = "seq_shard" if cfg.attn_seq_shard else "seq"
    q = partition.shard_act(q, "batch", q_seq, "heads", None)
    o = attn_ops.flash_attention(q, k, v, causal=True, scale=softmax_scale(cfg))
    if cfg.attn_seq_shard:
        o = partition.shard_act(o, "batch", "seq_shard", "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)
    return (out, latent) if return_cache else (out, None)


def mla_attention_decode(
    p,
    x: jnp.ndarray,                       # (B, 1, D)
    latent_cache: jnp.ndarray,            # (B, S, kv_lora + rope)
    pos: jnp.ndarray,
    cfg: ModelConfig,
):
    """Absorbed decode: MQA over the compressed cache."""
    m = cfg.mla
    vec = pos.ndim == 1
    positions = pos[:, None] if vec else pos[None]
    q_nope, q_rope = _queries(p, x, cfg, positions=positions)
    c_kv, k_rope = _latent_kv(p, x, cfg, positions=positions)
    row = jnp.concatenate([c_kv, k_rope], axis=-1).astype(latent_cache.dtype)  # (B,1,·)
    if vec:
        latent_cache = latent_cache.at[jnp.arange(latent_cache.shape[0]), pos].set(row[:, 0])
    else:
        latent_cache = jax.lax.dynamic_update_slice_in_dim(latent_cache, row, pos, axis=1)
    with jax.named_scope("mla_absorb"):
        # absorb W_uk into the query: q_lat (B, 1, H, kv_lora)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])
        q_full = jnp.concatenate([q_lat, q_rope], axis=-1)          # (B,1,H,lora+rope)
    # one latent head: the key is the whole row, the value its first kv_lora
    o_lat = attn_ops.decode_attention(
        q_full, latent_cache[:, :, None, :], None, pos,
        scale=softmax_scale(cfg), dv=m.kv_lora_rank,
    )                                                                # (B,1,H,lora)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bshr,rhk->bshk", o_lat, p["wuv"])            # absorb W_uv
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, latent_cache
