"""KV-cache utilities: sizing, slot insertion for continuous batching."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig


def cache_bytes(cfg: ModelConfig, batch: int, seq_len: int) -> int:
    """Analytical decode-state footprint (bytes) — the serving-capacity
    planner for admission control and the roofline memory term."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.mla is not None:
            per_tok = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
        else:
            per_tok = 2 * cfg.n_kv_heads * cfg.hd
        total = cfg.n_layers * batch * seq_len * per_tok * itemsize
        if cfg.family == "encdec":
            total += cfg.n_layers * batch * cfg.enc_seq * 2 * cfg.n_kv_heads * cfg.hd * itemsize
        return total
    if cfg.family == "encdec":
        per_tok = 2 * cfg.n_kv_heads * cfg.hd
        return cfg.n_layers * batch * (seq_len + cfg.enc_seq) * per_tok * itemsize
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    H = s.n_heads(cfg.d_model)
    conv = (s.conv_kernel - 1) * (d_in + 2 * s.n_groups * s.d_state) * itemsize
    ssm = H * s.head_dim * s.d_state * 4  # fp32 state
    per_layer = (conv + ssm) * batch
    if cfg.family == "ssm":
        return cfg.n_layers * per_layer
    # hybrid: mamba states + shared-attn KV per group
    G = cfg.n_layers // cfg.shared_attn_every
    attn = G * batch * seq_len * 2 * cfg.n_kv_heads * cfg.hd * itemsize
    return cfg.n_layers * per_layer + attn


def insert_sequence(batched_cache: Any, seq_cache: Any, slot, batch_axis: int = 1) -> Any:
    """Place a single-sequence cache (batch dim 1) into slot `slot` of a
    batched cache. Caches are stacked over layers on axis 0, so the batch
    axis is 1 by convention. `slot` may be traced: one compiled program
    serves every slot."""

    def put(dst, src):
        # pad src seq dims up to dst
        pads = []
        for d in range(src.ndim):
            if d == batch_axis or src.shape[d] == dst.shape[d]:
                pads.append((0, 0))
            else:
                pads.append((0, dst.shape[d] - src.shape[d]))
        src = jnp.pad(src, pads).astype(dst.dtype)
        return jax.lax.dynamic_update_slice_in_dim(dst, src, slot, axis=batch_axis)

    return jax.tree.map(put, batched_cache, seq_cache)


def summarize(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    b = cache_bytes(cfg, batch, seq_len)
    return {
        "bytes": int(b),
        "gib": round(b / 2**30, 3),
        "bytes_per_seq": int(b / max(batch, 1)),
    }
