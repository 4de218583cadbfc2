"""Per-family transformer blocks: init / train-apply / decode-apply / cache.

A "layer" here is the unit the model stack scans over. Families:
  dense | vlm : (MLA or GQA) attention + SwiGLU MLP
  moe         : (MLA or GQA) attention + routed-expert FFN (+ shared
                experts); a config's first_dense_layers keep the SwiGLU MLP
  ssm         : Mamba2 block
  hybrid      : Mamba2 layers; the *shared* attention block lives in model.py
  encdec      : encoder layer (bidir attn + GELU MLP) and
                decoder layer (causal self-attn + cross-attn + GELU MLP)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import partition
from . import attention, layers, mamba2, mla, moe


def _residual_enter(h, cfg: ModelConfig):
    if cfg.sequence_parallel:
        return partition.shard_act(h, "batch", "seq_shard", None)
    return partition.shard_act(h, "batch", "seq", None)


# ---------------------------------------------------------------- dense / moe
def _routed(cfg: ModelConfig, dense_ffn: bool) -> bool:
    return cfg.family == "moe" and not dense_ffn


def init_decoder_layer(key, cfg: ModelConfig, dense_ffn: bool = False):
    k1, k2 = jax.random.split(key)
    if cfg.mla is not None:
        attn_p, attn_s = mla.init_mla(k1, cfg)
    else:
        attn_p, attn_s = attention.init_attention(k1, cfg)
    n1, n1s = layers.init_rmsnorm(cfg.d_model)
    n2, n2s = layers.init_rmsnorm(cfg.d_model)
    if _routed(cfg, dense_ffn):
        ffn_p, ffn_s = moe.init_moe(k2, cfg)
    else:
        ffn_p, ffn_s = layers.init_swiglu(k2, cfg.d_model, cfg.d_ff, layers.dtype_of(cfg))
    params = {"attn": attn_p, "ffn": ffn_p, "ln1": n1, "ln2": n2}
    specs = {"attn": attn_s, "ffn": ffn_s, "ln1": n1s, "ln2": n2s}
    return params, specs


def decoder_layer(
    p, h: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray, dense_ffn: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[tuple], jnp.ndarray]:
    """Train/prefill. Returns (h, aux_loss, kv_for_cache, routing counts
    as ``moe.moe_layer`` gives them)."""
    h = _residual_enter(h, cfg)
    hn = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        a, kv = mla.mla_attention(p["attn"], hn, cfg, positions=positions, return_cache=True)
    else:
        a, kv = attention.self_attention(
            p["attn"], hn, cfg, positions=positions, causal=True, return_kv=True
        )
    h = h + a
    f, aux, counts = _ffn(p, h, cfg, dense_ffn)
    return h + f, aux, kv, counts


def _ffn(p, h, cfg: ModelConfig, dense_ffn: bool):
    hn = layers.rmsnorm(h, p["ln2"], cfg.norm_eps)
    if _routed(cfg, dense_ffn):
        return moe.moe_layer(hn, p["ffn"], cfg)
    # a layer with no routed experts: no choices, no expert rows
    no_counts = (jnp.zeros(h.shape[0] * h.shape[1], jnp.int32), jnp.int32(0))
    return layers.swiglu(hn, p["ffn"]), jnp.float32(0.0), no_counts


def decoder_layer_decode(
    p, h: jnp.ndarray, cache: dict, pos: jnp.ndarray, cfg: ModelConfig,
    dense_ffn: bool = False,
) -> Tuple[jnp.ndarray, dict, jnp.ndarray]:
    """One token per row. Returns (h, new_cache, routing counts)."""
    hn = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        a, latent = mla.mla_attention_decode(p["attn"], hn, cache["latent"], pos, cfg)
        new_cache = {"latent": latent}
    else:
        a, (k, v) = attention.self_attention_decode(
            p["attn"], hn, cache["k"], cache["v"], pos, cfg
        )
        new_cache = {"k": k, "v": v}
    h = h + a
    f, _, counts = _ffn(p, h, cfg, dense_ffn)
    return h + f, new_cache, counts


def init_decoder_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Zero per-layer cache + logical specs. KV heads shard over `model` when
    divisible; otherwise the sequence dim takes the model axis (seq-sharded
    cache for the flash-decoding combine)."""
    dt = layers.dtype_of(cfg)
    if cfg.mla is not None:
        m = cfg.mla
        # one row [c_kv, k_rope] per token: the decode kernel's key and value
        cache = {"latent": jnp.zeros((batch, cache_len, m.kv_lora_rank + m.qk_rope_dim), dt)}
        specs = {"latent": ("batch", "seq_shard", None)}
        return cache, specs
    kv_div = _kv_heads_shardable(cfg)
    seq_name = "seq" if kv_div else "seq_shard"
    cache = {
        "k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, cfg.hd), dt),
        "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, cfg.hd), dt),
    }
    specs = {
        "k": ("batch", seq_name, "kv_heads", None),
        "v": ("batch", seq_name, "kv_heads", None),
    }
    return cache, specs


def _kv_heads_shardable(cfg: ModelConfig) -> bool:
    ctx = partition.current()
    if ctx is None or ctx.mesh is None:
        return True
    size = ctx.mesh.shape.get("model", 1)
    return size <= 1 or cfg.n_kv_heads % size == 0


# ------------------------------------------------------------------------ ssm
def init_ssm_layer(key, cfg: ModelConfig):
    m_p, m_s = mamba2.init_mamba2(key, cfg)
    n, ns = layers.init_rmsnorm(cfg.d_model)
    return {"mamba": m_p, "ln": n}, {"mamba": m_s, "ln": ns}


def ssm_layer(p, h, cfg: ModelConfig, *, return_state: bool = False):
    h = _residual_enter(h, cfg)
    hn = layers.rmsnorm(h, p["ln"], cfg.norm_eps)
    y, state = mamba2.mamba2_block(p["mamba"], hn, cfg, return_state=return_state)
    return h + y, state


def ssm_layer_decode(p, h, state: dict, cfg: ModelConfig):
    hn = layers.rmsnorm(h, p["ln"], cfg.norm_eps)
    y, new_state = mamba2.mamba2_decode(p["mamba"], hn, state, cfg)
    return h + y, new_state


# --------------------------------------------------------------------- encdec
def init_encoder_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    attn_p, attn_s = attention.init_attention(k1, cfg)
    mlp_p, mlp_s = layers.init_gelu_mlp(k2, cfg.d_model, cfg.d_ff, layers.dtype_of(cfg))
    n1, n1s = layers.init_layernorm(cfg.d_model)
    n2, n2s = layers.init_layernorm(cfg.d_model)
    return (
        {"attn": attn_p, "mlp": mlp_p, "ln1": n1, "ln2": n2},
        {"attn": attn_s, "mlp": mlp_s, "ln1": n1s, "ln2": n2s},
    )


def encoder_layer(p, h, cfg: ModelConfig):
    h = _residual_enter(h, cfg)
    hn = layers.layernorm(h, p["ln1"], cfg.norm_eps)
    a, _ = attention.self_attention(p["attn"], hn, cfg, positions=None, causal=False)
    h = h + a
    hn = layers.layernorm(h, p["ln2"], cfg.norm_eps)
    return h + layers.gelu_mlp(hn, p["mlp"])


def init_cross_decoder_layer(key, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    self_p, self_s = attention.init_attention(k1, cfg)
    cross_p, cross_s = attention.init_attention(k2, cfg, cross=True)
    mlp_p, mlp_s = layers.init_gelu_mlp(k3, cfg.d_model, cfg.d_ff, layers.dtype_of(cfg))
    n1, n1s = layers.init_layernorm(cfg.d_model)
    n2, n2s = layers.init_layernorm(cfg.d_model)
    n3, n3s = layers.init_layernorm(cfg.d_model)
    return (
        {"self": self_p, "cross": cross_p, "mlp": mlp_p, "ln1": n1, "ln2": n2, "ln3": n3},
        {"self": self_s, "cross": cross_s, "mlp": mlp_s, "ln1": n1s, "ln2": n2s, "ln3": n3s},
    )


def cross_decoder_layer(p, h, enc_out, cfg: ModelConfig):
    """Train/prefill decoder layer. Returns (h, (self_k, self_v, cross_k, cross_v))."""
    h = _residual_enter(h, cfg)
    hn = layers.layernorm(h, p["ln1"], cfg.norm_eps)
    a, self_kv = attention.self_attention(p["self"], hn, cfg, positions=None, causal=True,
                                          return_kv=True)
    h = h + a
    hn = layers.layernorm(h, p["ln2"], cfg.norm_eps)
    c, cross_kv = attention.cross_attention(p["cross"], hn, kv_source=enc_out, cfg=cfg)
    h = h + c
    hn = layers.layernorm(h, p["ln3"], cfg.norm_eps)
    return h + layers.gelu_mlp(hn, p["mlp"]), (self_kv, cross_kv)


def cross_decoder_layer_decode(p, h, cache: dict, pos, cfg: ModelConfig):
    hn = layers.layernorm(h, p["ln1"], cfg.norm_eps)
    a, (k, v) = attention.self_attention_decode(p["self"], hn, cache["k"], cache["v"], pos, cfg)
    h = h + a
    hn = layers.layernorm(h, p["ln2"], cfg.norm_eps)
    c, _ = attention.cross_attention(
        p["cross"], hn, kv_cache=(cache["cross_k"], cache["cross_v"]), cfg=cfg
    )
    h = h + c
    hn = layers.layernorm(h, p["ln3"], cfg.norm_eps)
    h = h + layers.gelu_mlp(hn, p["mlp"])
    new_cache = dict(cache)
    new_cache.update(k=k, v=v)
    return h, new_cache
