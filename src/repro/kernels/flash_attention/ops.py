"""Dispatching wrapper: Pallas flash attention on TPU, jnp reference elsewhere.

``impl``: "auto" (pallas on TPU backends, ref otherwise), "pallas",
"pallas_interpret" (kernel body on CPU — used by the validation tests), "ref".
"""
from __future__ import annotations

from typing import Optional

import jax

from . import ref


# f32 bytes of one (block_k, KV, hd) K/V block the decode kernel may hold.
# On a TPU v5e at qwen1.5-0.5b's widths (32 slots x 1536) 128-position
# blocks were as fast as 256 and 512 with every slot full, and faster with
# few live positions: a slot's last block is read whole, live or not.
DECODE_BLOCK_BYTES = 512 * 1024


def decode_block_k(seq_len: int, n_kv_heads: int, head_dim: int) -> int:
    """Cache positions per block of the decode kernel: the largest of 512,
    256 and 128 whose float32 (block, KV, hd) block fits DECODE_BLOCK_BYTES
    (128 regardless), and never more than the cache holds."""
    per_pos = n_kv_heads * head_dim * 4
    block = next((b for b in (512, 256) if b * per_pos <= DECODE_BLOCK_BYTES), 128)
    return min(block, seq_len)


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    q_offset=None,
    kv_len=None,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
):
    """GQA attention. q, k (B,Sq,H,hd), (B,Skv,KV,hd); v (B,Skv,KV,dv), dv
    may differ from hd (MLA's prefill: qk 192, v 128) -> (B,Sq,H,dv)."""
    if impl == "auto":
        impl = _default_impl()
    if impl == "ref":
        return ref.mha_reference(
            q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len, scale=scale
        )
    from . import kernel  # deferred: pallas import is TPU-lowering-only

    return kernel.flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len, scale=scale,
        block_q=block_q, block_k=block_k, interpret=(impl == "pallas_interpret"),
    )


def decode_attention(q, k_cache, v_cache, pos, *, scale=None, dv=None, impl: str = "auto"):
    """Single-token attention against a cache; entries <= pos are valid.
    ``v_cache=None`` with ``dv``: the values are the first `dv` columns of
    each key row (MLA's latent is both key and value), read once."""
    if impl == "auto":
        impl = _default_impl()
    if impl == "ref":
        if v_cache is None:
            v_cache = k_cache[..., :dv]
        return ref.decode_attention_reference(q, k_cache, v_cache, pos, scale=scale)
    from . import kernel

    return kernel.decode_attention_pallas(
        q, k_cache, v_cache, pos, scale=scale, dv=dv, interpret=(impl == "pallas_interpret")
    )
