"""Operations and bytes the algorithm needs, from shapes and positions.

These count the work a correct program has to do, not what a kernel
executes: decode attention to each slot's own position, logits only where
a token is served. A share of a
peak built on them cannot pass 100% for a correct program, whatever
implements the step. Matrix products count 2 operations per multiply-add;
norms, biases, softmax and RoPE are left out, as MFU conventionally does.
"""
from __future__ import annotations

from typing import Dict, Iterable


BF16 = 2


def layer_matmul_params(s: Dict[str, int]) -> int:
    D, H, KV, hd, F = s["D"], s["H"], s["KV"], s["hd"], s["F"]
    return D * hd * (2 * H + 2 * KV) + 3 * D * F


def token_matmul_flops(s: Dict[str, int]) -> int:
    """One token through every layer's projections and the MLP."""
    return 2 * s["L"] * layer_matmul_params(s)


def logits_flops(s: Dict[str, int]) -> int:
    return 2 * s["D"] * s["V"]


def attn_flops(s: Dict[str, int], pairs: int) -> int:
    """QK^T and PV over every layer, for `pairs` (query, key) pairs."""
    return 2 * 2 * s["L"] * s["H"] * s["hd"] * pairs


def decode_token_flops(s: Dict[str, int], kv_len: int) -> int:
    """One decode token whose query attends to `kv_len` cached positions."""
    return token_matmul_flops(s) + logits_flops(s) + attn_flops(s, kv_len)


def decode_attention_kernel(s: Dict[str, int], kv_lens: Iterable[int]) -> Dict[str, float]:
    """Decode attention over every layer for queries at `kv_lens`: the keys
    and values each query needs, read once, plus q and o."""
    L, H, KV, hd = s["L"], s["H"], s["KV"], s["hd"]
    flops = bytes_ = 0
    for n in kv_lens:
        flops += 2 * 2 * L * H * hd * n
        bytes_ += L * (2 * n * KV * hd + 2 * H * hd) * BF16
    return {"flops": float(flops), "bytes": float(bytes_)}


def roofline_time(work: Dict[str, float], peaks: Dict[str, float]) -> Dict[str, float]:
    """The least time the chip could take, and which bound sets it."""
    t_c = work["flops"] / peaks["bf16_flops"]
    t_m = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}
