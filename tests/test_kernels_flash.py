"""Pallas flash-attention kernel vs the pure-jnp oracle: shape/dtype/causal/
GQA sweeps in interpret mode (assignment requirement: per-kernel allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.kernel import (
    decode_attention_pallas,
    flash_attention_pallas,
)
from repro.kernels.flash_attention.ops import decode_block_k

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dt):
    return jax.random.normal(key, shape, dt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,H,KV,hd",
    [
        (1, 64, 64, 4, 4, 32),     # MHA
        (2, 128, 128, 8, 2, 64),   # GQA 4:1
        (1, 96, 96, 6, 1, 16),     # MQA, non-pow2 heads
        (1, 100, 132, 4, 2, 32),   # unaligned seq (padding path)
        (2, 32, 256, 4, 4, 64),    # Skv >> Sq
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref(B, Sq, Skv, H, KV, hd, causal, dtype, key):
    if causal and Sq != Skv:
        pytest.skip("causal sweep uses square shapes")
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (B, Sq, H, hd), dtype)
    k = _rand(ks[1], (B, Skv, KV, hd), dtype)
    v = _rand(ks[2], (B, Skv, KV, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=32, block_k=32,
                                 interpret=True)
    expected = ref.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        out.astype(jnp.float32), expected.astype(jnp.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


@pytest.mark.parametrize("block", [16, 64, 128])
def test_flash_block_shape_invariance(block, key):
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (1, 128, 4, 32), jnp.float32)
    k = _rand(ks[1], (1, 128, 4, 32), jnp.float32)
    v = _rand(ks[2], (1, 128, 4, 32), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=block, block_k=block,
                                 interpret=True)
    expected = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)


def test_flash_kv_len_masking(key):
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (1, 16, 2, 16), jnp.float32)
    k = _rand(ks[1], (1, 64, 2, 16), jnp.float32)
    v = _rand(ks[2], (1, 64, 2, 16), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=False, kv_len=jnp.int32(20),
                                 block_q=16, block_k=16, interpret=True)
    expected = ref.mha_reference(q, k, v, causal=False, kv_len=jnp.int32(20))
    np.testing.assert_allclose(out, expected, rtol=2e-5, atol=2e-5)


def test_flash_q_offset_decode_window(key):
    """q_offset shifts absolute positions (used when decoding a block of
    suffix tokens against a longer cache)."""
    ks = jax.random.split(key, 3)
    S = 64
    q_full = _rand(ks[0], (1, S, 2, 16), jnp.float32)
    k = _rand(ks[1], (1, S, 2, 16), jnp.float32)
    v = _rand(ks[2], (1, S, 2, 16), jnp.float32)
    full = ref.mha_reference(q_full, k, v, causal=True)
    tail = flash_attention_pallas(
        q_full[:, 48:], k, v, causal=True, q_offset=jnp.int32(48),
        block_q=16, block_k=16, interpret=True,
    )
    np.testing.assert_allclose(tail, full[:, 48:], rtol=2e-5, atol=2e-5)


def _decode_case(key, B, S, H, KV, hd, dv, dtype):
    ks = jax.random.split(key, 3)
    return (_rand(ks[0], (B, 1, H, hd), dtype), _rand(ks[1], (B, S, KV, hd), dtype),
            _rand(ks[2], (B, S, KV, dv), dtype))


def _edges(S, KV, width):
    """Positions 0, bk-1, bk and S-1 for the block the kernel picks."""
    bk = decode_block_k(S, KV, width)
    assert S > bk
    return jnp.array([0, bk - 1, bk, S - 1], jnp.int32)


# (B, S, H, KV, hd, dv, pos): pos None means the block edges of _edges
DECODE_CASES = {
    "mha_edges_ragged": (4, 300, 8, 8, 128, 128, None),     # bk 128, S % bk = 44
    "gqa2_edges_ragged": (4, 600, 8, 4, 128, 128, None),    # bk 256
    "gqa4_edges_ragged": (4, 700, 8, 2, 64, 64, None),      # bk 512
    "gqa7_edges": (4, 1024, 14, 2, 64, 64, None),           # qwen2-0.5b's heads
    "mla_edges_ragged": (4, 400, 8, 1, 288, 256, None),     # latent: dk != dv
    "mha_scalar_pos": (2, 300, 8, 8, 128, 128, 200),
    "short_cache_scalar": (2, 48, 4, 2, 16, 16, 0),         # one block, S < bk
    "short_cache_mha_scalar": (2, 48, 4, 4, 16, 16, 23),
    "short_cache_vector": (3, 32, 4, 2, 16, 16, [3, 17, 31]),
    "mqa_short_vector": (2, 48, 4, 1, 16, 16, [47, 12]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decode_kernel_matches_ref(case, dtype, key):
    B, S, H, KV, hd, dv, pos = DECODE_CASES[case]
    q, kc, vc = _decode_case(key, B, S, H, KV, hd, dv, dtype)
    if pos is None:
        pos = _edges(S, KV, max(hd, dv))
    pos = jnp.asarray(pos, jnp.int32)
    out = decode_attention_pallas(q, kc, vc, pos, interpret=True)
    expected = ref.decode_attention_reference(q, kc, vc, pos)
    assert out.shape == (B, 1, H, dv)
    np.testing.assert_allclose(
        out.astype(jnp.float32), expected.astype(jnp.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,dk,dv,pos", [
    (4, 400, 8, 288, 256, None),          # minicpm3's latent: 256 + 32 rope
    (3, 300, 16, 576, 512, [0, 127, 299]),  # deepseek-v2's: 512 + 64 rope, 16 heads
])
def test_decode_kernel_values_from_key_rows(B, S, H, dk, dv, pos, dtype, key):
    """MLA's latent decode: no value cache, the values are the first dv
    columns of each key row, read once with it."""
    ks = jax.random.split(key, 2)
    q, kc = _rand(ks[0], (B, 1, H, dk), dtype), _rand(ks[1], (B, S, 1, dk), dtype)
    pos = _edges(S, 1, dk) if pos is None else jnp.asarray(pos, jnp.int32)
    out = decode_attention_pallas(q, kc, None, pos, dv=dv, interpret=True)
    expected = ref.decode_attention_reference(q, kc, kc[..., :dv], pos)
    assert out.shape == (B, 1, H, dv)
    np.testing.assert_allclose(
        out.astype(jnp.float32), expected.astype(jnp.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Sq,hd,dv", [(64, 192, 128), (100, 96, 64)])
def test_flash_value_width_differs(Sq, hd, dv, dtype, key):
    """MLA's prefill: q and k of qk_nope + qk_rope, v of v_head_dim
    (deepseek-v2: 192 and 128; minicpm3: 96 and 64)."""
    ks = jax.random.split(key, 3)
    q, k = _rand(ks[0], (1, Sq, 4, hd), dtype), _rand(ks[1], (1, Sq, 4, hd), dtype)
    v = _rand(ks[2], (1, Sq, 4, dv), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    expected = ref.mha_reference(q, k, v, causal=True)
    assert out.shape == (1, Sq, 4, dv)
    np.testing.assert_allclose(
        out.astype(jnp.float32), expected.astype(jnp.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


def test_causality_property(key):
    """Changing future keys/values must not change past outputs."""
    ks = jax.random.split(key, 4)
    q = _rand(ks[0], (1, 64, 2, 16), jnp.float32)
    k = _rand(ks[1], (1, 64, 2, 16), jnp.float32)
    v = _rand(ks[2], (1, 64, 2, 16), jnp.float32)
    out1 = flash_attention_pallas(q, k, v, causal=True, block_q=16, block_k=16,
                                  interpret=True)
    k2 = k.at[:, 40:].set(_rand(ks[3], (1, 24, 2, 16), jnp.float32))
    out2 = flash_attention_pallas(q, k2, v, causal=True, block_q=16, block_k=16,
                                  interpret=True)
    np.testing.assert_allclose(out1[:, :40], out2[:, :40], rtol=1e-6, atol=1e-6)
    assert not np.allclose(out1[:, 41:], out2[:, 41:])
