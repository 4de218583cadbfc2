"""Pallas TPU fused residual-add + RMSNorm.

One (block_rows x D) tile per grid step: the add, the fp32 square-mean
reduction, the rsqrt and the scale all happen in VMEM; HBM sees exactly one
read of x/delta and one write of each output (the unfused XLA-CPU path
materializes the fp32 sum and the normalized intermediate separately —
visible in the dry-run's unfused byte counts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, d_ref, s_ref, res_ref, out_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    res = x + d
    var = jnp.mean(res * res, axis=-1, keepdims=True)
    normed = res * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    res_ref[...] = res.astype(res_ref.dtype)
    out_ref[...] = normed.astype(out_ref.dtype)


def fused_add_rmsnorm_pallas(
    x: jnp.ndarray,          # (..., D)
    delta: jnp.ndarray,
    scale: jnp.ndarray,      # (D,)
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
):
    orig_shape = x.shape
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    d2 = delta.reshape(-1, D)
    T = x2.shape[0]
    block_rows = min(block_rows, max(T, 8))
    pad = (-T) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        d2 = jnp.pad(d2, ((0, pad), (0, 0)))
    n_blocks = x2.shape[0] // block_rows

    res, out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, d2, scale)
    if pad:
        res, out = res[:T], out[:T]
    return res.reshape(orig_shape), out.reshape(orig_shape)
