"""Plain reference of DeepSeek-V2 (the DeepSeek-V2-Lite layout), at one
chip's share of its routed experts.

Written from the published modeling code (Hugging Face
``modeling_deepseek.py``, ``DeepseekV2ForCausalLM``) and config:

    x = embed[tokens]
    for each layer:
        h = rmsnorm(x) * ln1
        q = h Wq                                   (per head: 128 nope + 64 rope;
                                                    q_lora_rank null: no low-rank step)
        c = rmsnorm(h Wdkv) * kv_norm              (the 512-wide latent)
        k_pe = h Wkr                               (one 64-wide rope key for all heads)
        q_pe, k_pe = rope(q_pe), rope(k_pe)        (YaRN frequencies; adjacent pairs
                                                    rotate: the code gathers even then
                                                    odd dims, then rotate-half)
        k = [c Wuk, k_pe], v = c Wuv               (latent up-projected per head)
        a = softmax(q k^T * scale + causal mask) v, scale = 192^-0.5 * mscale^2,
            mscale = 0.1 * mscale_all_dim * ln(factor) + 1
        x = x + a Wo
        h = rmsnorm(x) * ln2
        layer < first_k_dense_replace:  x = x + (silu(h Wg) * (h Wi)) Wo
        else:  p = softmax(h Wrouter) over every routed expert (float32),
               the top num_experts_per_tok by p (greedy), weights p (no
               renormalization) * routed_scaling_factor;
               x = x + sum over the experts held here of weight * expert(h)
                     + shared(h)                   (no gate)
    logits = (rmsnorm(x) * final_norm) Wunembed    (untied)

in float32 at ``jax.default_matmul_precision("highest")``, attention in
query blocks so that a sequence of the cell's longest length fits on the
chip. It imports nothing of the system under test. It also makes the
weights from the seed, in the layout the served program takes them
(``init_params``).

Departures from the published code, each deliberate:

- The chip's share of an expert-parallel deployment: the router scores all
  ``router_width`` experts and picks the top k over them, and only the
  ``n_routed_experts`` experts held here (from ``first_held``) add their
  part; choices of experts held on other chips add nothing. The program
  is given the same share.
- The rope key's ``kv_a_proj_with_mqa`` is two matrices, Wdkv and Wkr, and
  ``kv_b_proj`` two, Wuk and Wuv: the same products, split.
- No auxiliary loss and no dropout (inference); no KV cache.

``rounding="float8"`` computes every matrix product with both operands
rounded to float8 (e4m3) under a per-tensor scale: the reference one
precision below the configuration's bfloat16, the control that the
comparison must fail. ``rounding="bfloat16"`` rounds them to the
configuration's own precision: a witness of the gaps that rounding alone
gives.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lm import _q8, key_from_seed

F32 = jnp.float32
# router logits of std ~4: a token's first choices carry most of its
# routing weight, as a trained router's do, so that the routed experts
# move the logits the check compares (at N(0, 1/D) the top six of 64 share
# ~0.2 of the weight and an expert's part hides under the shared experts')
ROUTER_STD = 4.0


def sizes(hp: Dict) -> Dict[str, int]:
    """The shapes, by the letters the counting functions use."""
    L, n_dense = hp["num_hidden_layers"], hp["first_k_dense_replace"]
    return {
        "D": hp["hidden_size"],
        "H": hp["num_attention_heads"],
        "R": hp["kv_lora_rank"],
        "r": hp["qk_rope_head_dim"],
        "nope": hp["qk_nope_head_dim"],
        "dv": hp["v_head_dim"],
        "F": hp["intermediate_size"],
        "Fe": hp["moe_intermediate_size"],
        "Fs": hp["moe_intermediate_size"] * hp["n_shared_experts"],
        "E": hp["router_width"],
        "Eh": hp["n_routed_experts"],
        "k": hp["num_experts_per_tok"],
        "V": hp["vocab_size"],
        "L": L,
        "Ld": n_dense,
        "Lm": L - n_dense,
    }


def first_held(hp: Dict) -> int:
    """The first routed expert this chip holds: its rank's share."""
    return int(hp["expert_parallel"]["rank"]) * int(hp["n_routed_experts"])


def _init(key, hp: Dict, dtype):
    s = sizes(hp)
    D, H, R, r, nope, dv = (s[k] for k in ("D", "H", "R", "r", "nope", "dv"))
    ks = iter(jax.random.split(key, 40))

    def normal(shape, std, dt=dtype):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(dt)

    def scale(shape):
        return jax.random.uniform(next(ks), shape, F32, 0.5, 1.5)

    def stack(n, ffn):
        return {
            "attn": {
                "wq": normal((n, D, H, nope + r), D ** -0.5),
                "wdkv": normal((n, D, R), D ** -0.5),
                "wkr": normal((n, D, r), D ** -0.5),
                "kv_norm": scale((n, R)),
                "wuk": normal((n, R, H, nope), R ** -0.5),
                "wuv": normal((n, R, H, dv), R ** -0.5),
                "wo": normal((n, H, dv, D), (H * dv) ** -0.5),
            },
            "ffn": ffn(n),
            "ln1": {"scale": scale((n, D))},
            "ln2": {"scale": scale((n, D))},
        }

    # wi is the up projection, wg the gate, wo the down projection
    def mlp(n, f, lead=()):
        return {"wi": normal((n, *lead, D, f), D ** -0.5),
                "wg": normal((n, *lead, D, f), D ** -0.5),
                "wo": normal((n, *lead, f, D), f ** -0.5)}

    def experts(n):
        return {"router": normal((n, D, s["E"]), ROUTER_STD * D ** -0.5, F32),
                **mlp(n, s["Fe"], (s["Eh"],)),
                "shared": mlp(n, s["Fs"])}

    return {
        "embed": {"tok": normal((s["V"], D), D ** -0.5)},
        "unembed": {"w": normal((D, s["V"]), D ** -0.5)},
        "final_norm": {"scale": scale((D,))},
        "dense_layers": stack(s["Ld"], lambda n: mlp(n, s["F"])),
        "layers": stack(s["Lm"], experts),
    }


def init_params(seed: int, hp: Dict, dtype=jnp.bfloat16):
    """Weights from the seed, made on the device in one jitted call."""
    fn = jax.jit(functools.partial(_init, hp=hp, dtype=dtype))
    return jax.block_until_ready(fn(key_from_seed(seed)))


_ROUND = {"float8": _q8, "bfloat16": lambda x: x.astype(jnp.bfloat16).astype(F32)}


def _mm(spec, a, b, rounding):
    if rounding:
        a, b = _ROUND[rounding](a), _ROUND[rounding](b)
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: Dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies, float64."""
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def softmax_scale(hp: Dict) -> float:
    rs = hp["rope_scaling"]
    q_head = hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]
    m = _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    return q_head ** -0.5 * m * m


def _rope(x, pos, hp):
    """x (S, heads, d): gather even then odd dims, rotate-half with YaRN's
    cos and sin (times mscale / mscale_all_dim's ratio)."""
    rs = hp["rope_scaling"]
    d = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(d, hp["rope_theta"], rs), F32)
    m = (_yarn_get_mscale(rs["factor"], rs["mscale"])
         / _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = pos[:, None].astype(F32) * inv[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(lp, h, pos, hp, rounding, q_block):
    s = sizes(hp)
    a = lp["attn"]
    eps = hp["rms_norm_eps"]
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, a["wq"], rounding)
    q_nope, q_pe = q[..., : s["nope"]], q[..., s["nope"]:]
    c = _rmsnorm(_mm("sd,dr->sr", h, a["wdkv"], rounding), a["kv_norm"], eps)
    k_pe = _mm("sd,dr->sr", h, a["wkr"], rounding)[:, None, :]
    q_pe, k_pe = _rope(q_pe, pos, hp), _rope(k_pe, pos, hp)
    k = jnp.concatenate([_mm("sr,rhk->shk", c, a["wuk"], rounding),
                         jnp.broadcast_to(k_pe, (S, s["H"], s["r"]))], axis=-1)
    v = _mm("sr,rhk->shk", c, a["wuv"], rounding)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    scale = softmax_scale(hp)
    block = q_block if S % q_block == 0 else S

    def one(args):
        qb, start = args
        sc = _mm("qhk,shk->hqs", qb, k, rounding) * scale
        causal = jnp.arange(S)[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return _mm("hqs,shk->qhk", w, v, rounding)

    qb = q.reshape(S // block, block, s["H"], -1)
    o = jax.lax.map(one, (qb, jnp.arange(S // block) * block)).reshape(S, s["H"], -1)
    return _mm("qhk,hkd->qd", o, a["wo"], rounding)


def _swiglu(f, h, rounding):
    gate = _mm("sd,df->sf", h, f["wg"], rounding)
    up = _mm("sd,df->sf", h, f["wi"], rounding)
    return _mm("sf,fd->sd", jax.nn.silu(gate) * up, f["wo"], rounding)


def _experts(f, h, hp, rounding):
    """The routed experts held here, each weighted by its routing weight
    (0 for a token that did not choose it), plus the shared experts."""
    s = sizes(hp)
    probs = jax.nn.softmax(_mm("sd,de->se", h, f["router"], rounding), axis=-1)
    topw, topi = jax.lax.top_k(probs, s["k"])
    topw = topw * hp["routed_scaling_factor"]
    out = _swiglu(f["shared"], h, rounding)
    first = first_held(hp)
    for e in range(s["Eh"]):
        w = jnp.sum(jnp.where(topi == first + e, topw, 0.0), axis=-1)
        expert = {n: f[n][e] for n in ("wi", "wg", "wo")}
        out = out + w[:, None] * _swiglu(expert, h, rounding)
    return out


def _hidden(params, tokens, hp, rounding, q_block):
    """Final-normed hidden states (S, D) of one sequence, float32; each
    layer's weights are read in float32 inside its own step."""
    eps = hp["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])

    def make_layer(routed):
        def layer(x, lp):
            lp = jax.tree.map(lambda a: a.astype(F32), lp)
            x = x + _attention(lp, _rmsnorm(x, lp["ln1"]["scale"], eps), pos, hp, rounding,
                               q_block)
            h = _rmsnorm(x, lp["ln2"]["scale"], eps)
            return x + (_experts(lp["ffn"], h, hp, rounding) if routed
                        else _swiglu(lp["ffn"], h, rounding)), None
        return layer

    x = params["embed"]["tok"][tokens].astype(F32)
    x, _ = jax.lax.scan(make_layer(False), x, params["dense_layers"])
    x, _ = jax.lax.scan(make_layer(True), x, params["layers"])
    return (_rmsnorm(x, params["final_norm"]["scale"].astype(F32), eps),
            params["unembed"]["w"])


_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
         "first_k_dense_replace", "num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
         "qk_nope_head_dim", "v_head_dim", "n_shared_experts", "router_width",
         "n_routed_experts", "num_experts_per_tok", "vocab_size", "rms_norm_eps",
         "rope_theta", "routed_scaling_factor")


def _hp_key(hp: Dict):
    """What the forward reads of `hp`, as a hashable static argument."""
    return (tuple((k, hp[k]) for k in _KEYS),
            tuple(sorted(hp["rope_scaling"].items())),
            int(hp["expert_parallel"]["rank"]))


def _hp_of(key) -> Dict:
    flat, rope, rank = key
    return {**dict(flat), "rope_scaling": dict(rope), "expert_parallel": {"rank": rank}}


@functools.partial(jax.jit, static_argnames=("hp_key", "rounding", "block"))
def _logit_stats(params, tokens, chosen, hp_key, rounding, block):
    """Per position: the reference's best logit, its logit of `chosen`, and
    the argmax of this forward's own logits."""
    hp = _hp_of(hp_key)
    h, unembed = _hidden(params, tokens, hp, rounding, block)
    S = tokens.shape[0]
    block = block if S % block == 0 else S
    w = unembed.astype(F32)

    def one(args):
        hb, cb = args
        lg = _mm("sd,dv->sv", hb, w, rounding)
        best = jnp.max(lg, axis=-1)
        at = jnp.take_along_axis(lg, cb[:, None], axis=-1)[:, 0]
        return best, at, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    best, at, top = jax.lax.map(one, (h.reshape(S // block, block, -1),
                                      chosen.reshape(S // block, block)))
    return best.reshape(S), at.reshape(S), top.reshape(S)


def logits(params, hp: Dict, tokens, rounding: Optional[str] = None,
           q_block: int = 512) -> np.ndarray:
    """Logits (S, V) of one sequence, float32 at highest precision."""
    with jax.default_matmul_precision("highest"):
        h, unembed = _hidden(params, jnp.asarray(tokens), hp, rounding, q_block)
        return np.asarray(_mm("sd,dv->sv", h, unembed.astype(F32), rounding))


def _padded(history, pad_to):
    history = np.asarray(history, np.int32)
    toks = np.zeros(pad_to, np.int32)
    toks[: len(history) - 1] = history[:-1]
    chosen = np.zeros(pad_to, np.int32)
    chosen[: len(history) - 1] = history[1:]
    return history, toks, chosen


def served_gaps(params, hp: Dict, history, prompt_len: int, pad_to: int,
                block: int = 512) -> np.ndarray:
    """For a sequence the program served (prompt, then its generated tokens),
    the gap at each generated token: the reference's best logit at that
    position minus the reference's logit of the token the program served.
    0 where the program served the reference's argmax."""
    history, toks, chosen = _padded(history, pad_to)
    n = len(history) - prompt_len
    with jax.default_matmul_precision("highest"):
        best, at, _ = _logit_stats(params, jnp.asarray(toks), jnp.asarray(chosen),
                                   _hp_key(hp), None, block)
    best, at = np.asarray(best), np.asarray(at)
    sl = slice(prompt_len - 1, prompt_len - 1 + n)
    return best[sl] - at[sl]


def control_gaps(params, hp: Dict, history, prompt_len: int, pad_to: int,
                 block: int = 512, rounding: str = "float8") -> np.ndarray:
    """The control's reading on the same sequence: at each served position,
    the gap of the token that the float8 forward (or the forward at
    `rounding`) puts first, measured under the float32 reference."""
    history, toks, _ = _padded(history, pad_to)
    n = len(history) - prompt_len
    with jax.default_matmul_precision("highest"):
        _, _, top = _logit_stats(params, jnp.asarray(toks), jnp.zeros(pad_to, jnp.int32),
                                 _hp_key(hp), rounding, block)
        best, at, _ = _logit_stats(params, jnp.asarray(toks), top, _hp_key(hp), None, block)
    best, at = np.asarray(best), np.asarray(at)
    sl = slice(prompt_len - 1, prompt_len - 1 + n)
    return best[sl] - at[sl]
