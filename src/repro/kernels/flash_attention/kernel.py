"""Pallas TPU flash attention (causal, GQA) with explicit BlockSpec tiling.

TPU-native adaptation: (block_q x hd) / (block_k x hd) tiles stream through
VMEM; the online-softmax accumulator/max/denominator live in VMEM scratch;
the MXU sees hardware-aligned (128-default) matmul tiles; q_offset / kv_len
arrive via scalar prefetch (SMEM). The S^2 score matrix never touches HBM —
this is the kernel the roofline memory model assumes on the TPU target.

Decode has a kernel of its own (`decode_attention_pallas`): one query row
per slot, a cache length per slot, and a memory-bound loop over the cache,
so all slots share one grid of only their live K/V blocks.

Validated against ref.mha_reference / ref.decode_attention_reference in
interpret mode (CPU) by tests/test_kernels_flash.py across shape/dtype/
causal/GQA sweeps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import decode_block_k
from .ref import NEG_INF


def _flash_kernel(
    meta_ref,     # scalar prefetch: (2,) int32 [q_offset, kv_len]
    q_ref,        # (1, block_q, hd)
    k_ref,        # (1, block_k, hd)
    v_ref,        # (1, block_k, dv)
    o_ref,        # (1, block_q, dv)
    acc_ref,      # (block_q, dv) f32 VMEM scratch
    m_ref,        # (block_q, 1) f32
    l_ref,        # (block_q, 1) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    q_offset = meta_ref[0]
    kv_len = meta_ref[1]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * block_q + jax.lax.iota(jnp.int32, block_q) + q_offset
    k_pos = ik * block_k + jax.lax.iota(jnp.int32, block_k)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                            # (bq, bk)
        mask = k_pos[None, :] < kv_len
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, 0] = m_new

    if causal:
        # tile is dead iff its lowest k position exceeds the tile's highest
        # absolute q position (q_offset is dynamic: evaluate inside pl.when)
        live = (ik * block_k) <= (iq * block_q + block_q - 1 + q_offset)

        @pl.when(live)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention_pallas(
    q: jnp.ndarray,            # (B, Sq, H, hd)
    k: jnp.ndarray,            # (B, Skv, KV, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset=None,
    kv_len=None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    dv = v.shape[-1]
    assert H % KV == 0, (H, KV)
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Skv, 8))

    qt = _pad_to(jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, hd), 1, block_q)
    kt = _pad_to(jnp.moveaxis(k, 2, 1).reshape(B * KV, Skv, hd), 1, block_k)
    vt = _pad_to(jnp.moveaxis(v, 2, 1).reshape(B * KV, Skv, dv), 1, block_k)
    Sq_p, Skv_p = qt.shape[1], kt.shape[1]
    n_q, n_k = Sq_p // block_q, Skv_p // block_k

    q_off = jnp.asarray(0 if q_offset is None else q_offset, jnp.int32)
    klen = jnp.asarray(Skv if kv_len is None else kv_len, jnp.int32)
    meta = jnp.stack([q_off, klen]).astype(jnp.int32)

    def kv_index(bh, iq, ik, meta):  # noqa: ARG001 — grid ids first, scalar ref last
        return ((bh // H) * KV + (bh % H) // G, ik, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k_blocks=n_k,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik, meta: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda bh, iq, ik, meta: (bh, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(meta, qt, kt, vt)
    out = out[:, :Sq].reshape(B, H, Sq, dv)
    return jnp.moveaxis(out, 1, 2)


def _decode_kernel(
    lens_ref,     # scalar prefetch: (B,) int32 valid cache positions per slot
    steps_ref,    # scalar prefetch: (B * n_k,) int32 slot * n_k + k-block, live steps first
    q_ref,        # (1, 1, H, hd)
    k_ref,        # (1, block_k, KV, hd)
    *refs,        # [v_ref (1, block_k, KV, dv)], o_ref (1, 1, H, dv), then
    #               scratch m_ref (H, 1), l_ref (H, 1), acc_ref (H, dv) f32
    scale: float,
    block_k: int,
    n_k_blocks: int,
    group: int,
    ragged_tail: bool,
    dv: int,
    v_in_k: bool,
):
    if v_in_k:            # the values are the first dv columns of each key row
        o_ref, m_ref, l_ref, acc_ref = refs
    else:
        v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    step = steps_ref[pl.program_id(0)]
    ik = step % n_k_blocks
    kv_len = lens_ref[step // n_k_blocks]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, bk, KV, _ = k_ref.shape
    # one row per (position, kv head): every query head scores every row,
    # and the mask keeps its own kv head's rows at live positions
    q = q_ref[0, 0].astype(jnp.float32)                                 # (H, hd)
    k = k_ref[0].astype(jnp.float32).reshape(bk * KV, -1)               # (bk*KV, hd)
    if v_in_k:
        v = k[:, :dv]                                                   # (bk*KV, dv)
    else:
        v = v_ref[0].astype(jnp.float32).reshape(bk * KV, -1)           # (bk*KV, dv)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                                           # (H, bk*KV)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    live = (row % KV == head // group) & (ik * block_k + row // KV < kv_len)
    s = jnp.where(live, s, NEG_INF)
    if ragged_tail:  # the cache's last block runs past its end: those rows are not data
        row_v = jax.lax.broadcasted_iota(jnp.int32, (bk * KV, 1), 0)
        v = jnp.where(ik * block_k + row_v // KV < kv_len, v, 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when((ik + 1) * block_k >= kv_len)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, pos, *, scale=None, dv=None,
                            interpret=False):
    """Single-token attention for every slot in one kernel.

    q (B, 1, H, hd); k_cache (B, S, KV, hd); v_cache (B, S, KV, dv), dv may
    differ from hd; or v_cache None and the values the first `dv` columns of
    each key row, read with it (MLA's latent decode: one latent head is the
    key, and its first kv_lora columns the value); pos scalar or (B,): slot b attends
    to cache positions 0..pos[b]. K/V blocks are read from the cache as it
    lies, and only the live ones: the grid has one step per (slot, k-block)
    below the slot's length, slot by slot, sum(cdiv(pos + 1, block_k))
    steps in all, so an idle slot costs one block and one step.
    """
    B, _, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    v_in_k = v_cache is None
    dv = dv if v_in_k else v_cache.shape[-1]
    assert H % KV == 0, (H, KV)
    scale = scale if scale is not None else hd ** -0.5
    block_k = decode_block_k(S, KV, max(hd, dv))
    n_k = pl.cdiv(S, block_k)
    lens = jnp.broadcast_to(jnp.asarray(pos, jnp.int32) + 1, (B,))
    # step i -> (slot, k-block): slots in order, each over its live blocks
    n_live = (lens + block_k - 1) // block_k
    ends = jnp.cumsum(n_live)
    i = jnp.arange(B * n_k, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1)
    steps = slot * n_k + i - (ends - n_live)[slot]

    def kv_index(i, lens, steps):  # noqa: ARG001 — grid ids first, scalar refs last
        return (steps[i] // n_k, steps[i] % n_k, 0, 0)

    def slot_index(i, lens, steps):  # noqa: ARG001
        return (steps[i] // n_k, 0, 0, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_k_blocks=n_k,
        group=H // KV, ragged_tail=S % block_k != 0, dv=dv, v_in_k=v_in_k,
    )
    in_specs = [
        pl.BlockSpec((1, 1, H, hd), slot_index),
        pl.BlockSpec((1, block_k, KV, hd), kv_index),
    ]
    operands = (q, k_cache)
    if not v_in_k:
        in_specs.append(pl.BlockSpec((1, block_k, KV, dv), kv_index))
        operands += (v_cache,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ends[-1],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, H, dv), slot_index),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lens, steps, *operands)
