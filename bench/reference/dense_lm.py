"""Plain reference of the dense decoder family (Qwen1.5 / Qwen2 layout).

Written from the published description (Hugging Face ``Qwen2ForCausalLM``):

    x = embed[tokens]
    for each layer:
        h = rmsnorm(x) * ln1
        q, k, v = h Wq + bq, h Wk + bk, h Wv + bv        (per head)
        q, k = rope(q), rope(k)                          (rotate-half, theta)
        a = softmax(q k^T / sqrt(head_dim) + causal mask) v
        x = x + a Wo                                     (no output bias)
        h = rmsnorm(x) * ln2
        x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * final_norm) embed^T           (tied embeddings)

in float32 at ``jax.default_matmul_precision("highest")``. It imports
nothing of the system under test. It also makes the weights from the seed,
in the layout the served program takes them (``init_params``), so the
program gets its weights from here and the reference reads the same pytree.

``control=True`` computes every matrix product with both operands rounded to
float8 (e4m3) under a per-tensor scale: the reference one precision below
the configuration's bfloat16, the control that the comparison must fail.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def sizes(hp: Dict) -> Dict[str, int]:
    d = hp["hidden_size"]
    h = hp["num_attention_heads"]
    return {
        "D": d,
        "H": h,
        "KV": hp["num_key_value_heads"],
        "hd": hp.get("head_dim", d // h),
        "F": hp["intermediate_size"],
        "V": hp["vocab_size"],
        "L": hp["num_hidden_layers"],
    }


def key_from_seed(seed: int):
    """A PRNG key from any non-negative integer seed, wider ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _init(key, hp: Dict, dtype):
    s = sizes(hp)
    D, H, KV, hd, F, V, L = (s[k] for k in ("D", "H", "KV", "hd", "F", "V", "L"))
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dt=dtype):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(dt)

    def scale(shape):
        return jax.random.uniform(next(ks), shape, F32, 0.5, 1.5)

    return {
        "embed": {"tok": normal((V, D), D ** -0.5)},
        "final_norm": {"scale": scale((D,))},
        "layers": {
            "attn": {
                "wq": normal((L, D, H, hd), D ** -0.5),
                "wk": normal((L, D, KV, hd), D ** -0.5),
                "wv": normal((L, D, KV, hd), D ** -0.5),
                "wo": normal((L, H, hd, D), (H * hd) ** -0.5),
                "bq": normal((L, H, hd), 0.5),
                "bk": normal((L, KV, hd), 0.5),
                "bv": normal((L, KV, hd), 0.5),
            },
            # wi is the up projection, wg the gate, wo the down projection
            "ffn": {
                "wi": normal((L, D, F), D ** -0.5),
                "wg": normal((L, D, F), D ** -0.5),
                "wo": normal((L, F, D), F ** -0.5),
            },
            "ln1": {"scale": scale((L, D))},
            "ln2": {"scale": scale((L, D))},
        },
    }


def init_params(seed: int, hp: Dict, dtype=jnp.bfloat16):
    """Weights from the seed, made on the device in one jitted call."""
    fn = jax.jit(functools.partial(_init, hp=hp, dtype=dtype))
    return jax.block_until_ready(fn(key_from_seed(seed)))


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = FP8_MAX / amax
    return (x * s).astype(FP8).astype(F32) / s


def _mm(spec, a, b, control):
    if control:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE over the last axis; x (S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def _hidden(params, tokens, hp, control):
    """Final-normed hidden states (S, D) of one sequence, float32."""
    s = sizes(hp)
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    p = jax.tree.map(lambda a: a.astype(F32), params)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    rep = s["H"] // s["KV"]

    def layer(x, lp):
        a = lp["attn"]
        h = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _mm("sd,dhk->shk", h, a["wq"], control) + a["bq"]
        k = _mm("sd,dhk->shk", h, a["wk"], control) + a["bk"]
        v = _mm("sd,dhk->shk", h, a["wv"], control) + a["bv"]
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        scores = _mm("qhk,shk->hqs", q, k, control) / np.sqrt(s["hd"])
        scores = jnp.where(causal[None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        o = _mm("hqs,shk->qhk", w, v, control)
        x = x + _mm("qhk,hkd->qd", o, a["wo"], control)
        f = lp["ffn"]
        h = _rmsnorm(x, lp["ln2"]["scale"], eps)
        gate = _mm("sd,df->sf", h, f["wg"], control)
        up = _mm("sd,df->sf", h, f["wi"], control)
        x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, f["wo"], control)
        return x, None

    x = p["embed"]["tok"][tokens]
    x, _ = jax.lax.scan(layer, x, p["layers"])
    return _rmsnorm(x, p["final_norm"]["scale"], eps), p["embed"]["tok"]


@functools.partial(jax.jit, static_argnames=("hp_items", "control", "block"))
def _logit_stats(params, tokens, chosen, hp_items, control, block):
    """Per position: the reference's best logit, the reference's logit of
    `chosen`, and the argmax of this forward's own logits."""
    hp = dict(hp_items)
    h, emb = _hidden(params, tokens, hp, control)
    S = tokens.shape[0]
    block = block if S % block == 0 else S

    def one(args):
        hb, cb = args
        lg = _mm("sd,vd->sv", hb, emb, control)
        best = jnp.max(lg, axis=-1)
        at = jnp.take_along_axis(lg, cb[:, None], axis=-1)[:, 0]
        return best, at, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    hb = h.reshape(S // block, block, -1)
    cb = chosen.reshape(S // block, block)
    best, at, top = jax.lax.map(one, (hb, cb))
    return best.reshape(S), at.reshape(S), top.reshape(S)


def logits(params, hp: Dict, tokens, control: bool = False) -> np.ndarray:
    """Logits (S, V) of one sequence, float32 at highest precision."""
    with jax.default_matmul_precision("highest"):
        h, emb = _hidden(params, jnp.asarray(tokens), hp, control)
        return np.asarray(_mm("sd,vd->sv", h, emb, control))


def _hp_items(hp: Dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "vocab_size", "num_hidden_layers",
            "rms_norm_eps", "rope_theta")
    return tuple((k, hp[k]) for k in keys)


def served_gaps(params, hp: Dict, history, prompt_len: int, pad_to: int,
                block: int = 512) -> np.ndarray:
    """For a sequence the program served (prompt, then its generated tokens),
    the gap at each generated token: the reference's best logit at that
    position minus the reference's logit of the token the program served.
    0 where the program served the reference's argmax."""
    history = np.asarray(history, np.int32)
    n = len(history) - prompt_len          # served tokens
    toks = np.zeros(pad_to, np.int32)
    toks[: len(history) - 1] = history[:-1]
    chosen = np.zeros(pad_to, np.int32)
    chosen[: len(history) - 1] = history[1:]
    with jax.default_matmul_precision("highest"):
        best, at, _ = _logit_stats(params, jnp.asarray(toks), jnp.asarray(chosen),
                                   _hp_items(hp), False, block)
    best, at = np.asarray(best), np.asarray(at)
    sl = slice(prompt_len - 1, prompt_len - 1 + n)
    return best[sl] - at[sl]


def control_gaps(params, hp: Dict, history, prompt_len: int, pad_to: int,
                 block: int = 512) -> np.ndarray:
    """The control's reading on the same sequence: at each served position,
    the gap of the token that the float8 forward puts first, measured under
    the float32 reference."""
    history = np.asarray(history, np.int32)
    n = len(history) - prompt_len
    toks = np.zeros(pad_to, np.int32)
    toks[: len(history) - 1] = history[:-1]
    with jax.default_matmul_precision("highest"):
        _, _, top = _logit_stats(params, jnp.asarray(toks), jnp.zeros(pad_to, jnp.int32),
                                 _hp_items(hp), True, block)
        best, at, _ = _logit_stats(params, jnp.asarray(toks), top,
                                   _hp_items(hp), False, block)
    best, at = np.asarray(best), np.asarray(at)
    sl = slice(prompt_len - 1, prompt_len - 1 + n)
    return best[sl] - at[sl]
