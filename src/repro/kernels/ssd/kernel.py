"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU-native adaptation of the SSD block decomposition (arXiv:2405.21060 §6):
grid = (batch, heads, chunks) with the chunk axis sequential ("arbitrary"
semantics); the inter-chunk state (P x N) is carried in VMEM scratch across
grid steps — the recurrence never round-trips HBM. Within a chunk everything
is (chunk x chunk) / (chunk x P) matmuls on the MXU; cumulative sums are
computed as lower-triangular matmuls (MXU-friendly) rather than serial scans.

Validated against ref.ssd_reference in interpret mode by
tests/test_kernels_ssd.py across shape/dtype/chunk sweeps.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    A_ref,      # SMEM (H,) f32 decay rates, indexed by the head grid id
    x_ref,      # (1, 1, chunk, P)
    dt_ref,     # (1, 1, chunk, 1)
    B_ref,      # (1, 1, chunk, N)
    C_ref,      # (1, 1, chunk, N)
    y_ref,      # (1, 1, chunk, P)
    state_ref,  # out: (1, 1, P, N) — final state, written on last chunk
    h_ref,      # VMEM scratch: (P, N) f32 carried state
    *,
    chunk: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (c, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (c, 1)
    A = A_ref[pl.program_id(1)]                    # scalar
    Bm = B_ref[0, 0].astype(jnp.float32)           # (c, N)
    Cm = C_ref[0, 0].astype(jnp.float32)           # (c, N)

    dA = dt * A                                    # (c, 1)
    # cumulative sums as triangular matmuls (MXU-friendly, no serial scan);
    # the row form comes from contracting the same column with tril's rows.
    # In full float32: at the default precision the MXU rounds dA to bf16,
    # and the cumsum then disagrees with the exact `total` below by enough
    # to move exp(total - dA_cum), and so the final state, by about 1e-2.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    tril_incl = causal.astype(jnp.float32)         # i >= j
    dA_cum = jax.lax.dot_general(
        tril_incl, dA, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                              # (c, 1) inclusive cumsum
    dA_cum_row = jax.lax.dot_general(
        dA, tril_incl, (((0,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                              # (1, c) the same, as a row

    # L[i,j] = exp(sum_{j+1..i} dA) for i>=j else 0
    L = jnp.where(causal, jnp.exp(dA_cum - dA_cum_row), 0.0)

    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (c, c)
    dtx = x * dt                                   # (c, P)
    y_diag = jax.lax.dot_general(
        CB * L, dtx, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (c, P)

    # inter-chunk: read out carried state, then update it
    h = h_ref[...]                                 # (P, N)
    y_off = jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(dA_cum)                            # (c, P)

    total = jnp.sum(dA)                            # whole-chunk log decay
    decay_to_end = jnp.exp(total - dA_cum)         # (c, 1)
    chunk_state = jax.lax.dot_general(
        dtx * decay_to_end, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (P, N)
    h_new = h * jnp.exp(total) + chunk_state
    h_ref[...] = h_new

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        state_ref[0, 0] = h_new.astype(state_ref.dtype)


def ssd_pallas(
    x: jnp.ndarray,     # (B, S, H, P)
    dt: jnp.ndarray,    # (B, S, H)
    A: jnp.ndarray,     # (H,)
    B_: jnp.ndarray,    # (B, S, G, N)
    C_: jnp.ndarray,    # (B, S, G, N)
    *,
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,
    return_final_state: bool = False,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    assert initial_state is None, "kernel path supports zero initial state"
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G

    # heads and groups move ahead of the sequence so every block's last two
    # dims are (chunk, P|N|1): the TPU tiling the kernel's blocks must meet
    xt = jnp.moveaxis(x, 2, 1)                     # (B, H, S, P)
    dtt = jnp.moveaxis(dt, 2, 1)[..., None]        # (B, H, S, 1)
    Bt = jnp.moveaxis(B_, 2, 1)                    # (B, G, S, N)
    Ct = jnp.moveaxis(C_, 2, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    seq_block = lambda d: pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0))
    group_block = pl.BlockSpec(
        (1, 1, chunk, N), lambda b, h, ic, rep=rep: (b, h // rep, ic, 0)
    )
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            seq_block(P),
            seq_block(1),
            group_block,
            group_block,
        ],
        out_specs=[
            seq_block(P),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A.astype(jnp.float32), xt, dtt, Bt, Ct)
    y = jnp.moveaxis(y, 1, 2)
    return (y, state) if return_final_state else (y, None)
