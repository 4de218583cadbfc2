"""Operations and bytes that DeepSeek-V2's decode needs, from shapes,
positions and the routing counts, for one chip's share of its experts.

As ``flops.py`` does for the dense decoder, these count the work a correct
program has to do, not what it executes: attention to each slot's own
position in the absorbed (latent) form, the routed experts only for the
(token, choice) pairs that land on an expert held here, logits only where a
token is served. A share of a peak built on them cannot pass 100% for a
correct program. Matrix products count 2 operations per multiply-add;
norms, softmax and RoPE are left out. Letters as ``sizes`` gives them: D
hidden, H heads, R latent (kv_lora), r rope, nope, dv value head, F dense
MLP, Fe expert, Fs shared experts, E router width, Eh experts held, k
experts per token, V vocabulary, L layers of which Ld dense and Lm routed.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench.reference.deepseek_v2 import sizes  # noqa: F401 — the letters' source

BF16 = 2


def attn_proj_macs(s: Dict[str, int]) -> int:
    """One token through one layer's attention projections, absorbed form:
    queries, the latent and rope key, W_uk into the query, W_uv out of the
    latent, the output."""
    D, H, R, r, nope, dv = s["D"], s["H"], s["R"], s["r"], s["nope"], s["dv"]
    return D * H * (nope + r) + D * (R + r) + H * nope * R + H * R * dv + H * dv * D


def token_macs(s: Dict[str, int], held_pairs: float) -> float:
    """One token through every layer but attention to the cache, and the
    head: the dense layers' MLP, each expert layer's router and shared
    experts, and `held_pairs` routed (layer, choice) pairs on held experts."""
    D = s["D"]
    return (s["L"] * attn_proj_macs(s) + s["Ld"] * 3 * D * s["F"]
            + s["Lm"] * (D * s["E"] + 3 * D * s["Fs"])
            + held_pairs * 3 * D * s["Fe"] + D * s["V"])


def mla_attention_macs(s: Dict[str, int], kv_len: int) -> int:
    """Attention of one query over `kv_len` cached latent rows, every layer:
    scores over R + r columns, values over R, for each of H heads."""
    return s["L"] * s["H"] * (2 * s["R"] + s["r"]) * kv_len


def decode_token_flops(s: Dict[str, int], kv_len: int, held_pairs: float) -> float:
    """One decode token whose query attends to `kv_len` cached positions and
    whose choices put `held_pairs` (layer, choice) pairs on held experts."""
    return 2.0 * (token_macs(s, held_pairs) + mla_attention_macs(s, kv_len))


def held_pairs_per_token(s: Dict[str, int], held: int, every: int) -> float:
    """Routed pairs on held experts per token over all expert layers, from
    the counts of pairs on held experts and of all pairs."""
    return s["k"] * s["Lm"] * held / every


def mla_decode_attention(s: Dict[str, int], kv_lens: Iterable[int]) -> Dict[str, float]:
    """The latent decode attention for queries at `kv_lens`, every layer:
    each query's latent rows read once (they are key and value), plus q and
    o."""
    L, H, R, r = s["L"], s["H"], s["R"], s["r"]
    flops = bytes_ = 0
    for n in kv_lens:
        flops += 2 * mla_attention_macs(s, n)
        bytes_ += L * (n * (R + r) + H * (R + r) + H * R) * BF16
    return {"flops": float(flops), "bytes": float(bytes_)}
