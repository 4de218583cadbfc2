"""The DeepSeek-V2 cell's benchmark code on the CPU: the plain reference
against a float64 numpy forward, the counting functions against counts
made by hand, and the cell's correctness check, which must come out not correct under
the float8 control and under a planted fault (one held expert dropped)."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_mla_moe as fm
from bench import harness
from bench.reference import deepseek_v2 as ref
from bench.run import run_cell
from conftest import make_tiny_root, write_json

TINY = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
    "n_shared_experts": 2, "router_width": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "routed_scaling_factor": 1, "expert_parallel": {"rank": 1},
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}


# ------------------------------------------------------------ float64 numpy
def _np_forward(params, hp, tokens):
    """The published forward in float64 numpy, one position at a time for
    attention; experts by an explicit loop over tokens and choices."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    s = ref.sizes(hp)
    eps, rs = hp["rms_norm_eps"], hp["rope_scaling"]

    def norm(x, w):
        return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def silu(x):
        return x / (1 + np.exp(-x))

    d = s["r"]
    base, factor = hp["rope_theta"], rs["factor"]
    orig = rs["original_max_position_embeddings"]
    corr = [d * math.log(orig / (b * 2 * math.pi)) / (2 * math.log(base))
            for b in (rs["beta_fast"], rs["beta_slow"])]
    lo, hi = max(math.floor(corr[0]), 0), min(math.ceil(corr[1]), d - 1)
    inv = []
    for i in range(d // 2):
        extra = base ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        inv.append(extra / factor * ramp + extra * (1 - ramp))
    inv = np.asarray(inv)

    def rope(x, t):   # x (heads, d) at position t: rotate pairs (2i, 2i + 1)
        c, sn = np.cos(t * inv), np.sin(t * inv)
        ev, od = x[..., 0::2], x[..., 1::2]
        return np.concatenate([ev * c - od * sn, od * c + ev * sn], axis=-1)

    mscale = 0.1 * rs["mscale_all_dim"] * math.log(factor) + 1
    scale = (s["nope"] + s["r"]) ** -0.5 * mscale ** 2
    S = len(tokens)
    x = p["embed"]["tok"][tokens]
    layers = [("dense_layers", i) for i in range(s["Ld"])] + \
             [("layers", i) for i in range(s["Lm"])]
    for stack, i in layers:
        lp = jax.tree.map(lambda a: a[i], p[stack])
        a = lp["attn"]
        h = norm(x, lp["ln1"]["scale"])
        q = np.einsum("sd,dhk->shk", h, a["wq"])
        c = norm(h @ a["wdkv"], a["kv_norm"])
        kpe = h @ a["wkr"]
        k_nope = np.einsum("sr,rhk->shk", c, a["wuk"])
        v = np.einsum("sr,rhk->shk", c, a["wuv"])
        qs = [np.concatenate([q[t, :, :s["nope"]], rope(q[t, :, s["nope"]:], t)], -1)
              for t in range(S)]
        ks = [np.concatenate([k_nope[t], np.broadcast_to(rope(kpe[t], t), (s["H"], d))], -1)
              for t in range(S)]
        o = np.zeros((S, s["H"], s["dv"]))
        for t in range(S):
            sc = np.stack([np.sum(qs[t] * ks[u], -1) for u in range(t + 1)]) * scale
            w = np.exp(sc - sc.max(0))
            w /= w.sum(0)
            o[t] = np.einsum("uh,uhk->hk", w, v[: t + 1])
        x = x + np.einsum("shk,hkd->sd", o, a["wo"])
        h = norm(x, lp["ln2"]["scale"])
        f = lp["ffn"]

        def mlp(w, hh):
            return (silu(hh @ w["wg"]) * (hh @ w["wi"])) @ w["wo"]

        if stack == "dense_layers":
            x = x + mlp(f, h)
            continue
        logits = h @ f["router"]
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        first = ref.first_held(hp)
        out = mlp(f["shared"], h)
        for t in range(S):
            for e in np.argsort(-probs[t])[: s["k"]]:
                if first <= e < first + s["Eh"]:
                    w = {n: f[n][e - first] for n in ("wi", "wg", "wo")}
                    out[t] += probs[t, e] * mlp(w, h[t])
        x = x + out
    return norm(x, p["final_norm"]["scale"]) @ p["unembed"]["w"]


def test_reference_matches_float64_forward():
    params = ref.init_params(5, TINY, dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 256, 20).astype(np.int32)
    got = ref.logits(params, TINY, tokens, q_block=4)
    want = _np_forward(params, TINY, tokens)
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


# ------------------------------------------------------------ counts by hand
def test_counts_by_hand():
    s = fm.sizes(TINY)
    # D 64, H 4, R 32, r 8, nope 16, dv 16, F 96, Fe 32, Fs 64, E 16, Eh 4,
    # k 4, V 256, L 3 (1 dense, 2 routed)
    assert (s["Fs"], s["Ld"], s["Lm"]) == (64, 1, 2)
    # per layer: q 64x4x24 = 6144, latent + rope key 64x40 = 2560, W_uk into
    # the query 4x16x32 = 2048, W_uv out 4x32x16 = 2048, output 4x16x64 = 4096
    assert fm.attn_proj_macs(s) == 6144 + 2560 + 2048 + 2048 + 4096
    # 3 layers' projections 50688; dense MLP 3x64x96 = 18432; per routed
    # layer router 64x16 = 1024 and shared 3x64x64 = 12288; 2.5 held pairs x
    # 3x64x32; head 64x256 = 16384
    want = 3 * 16896 + 18432 + 2 * (1024 + 12288) + 2.5 * 6144 + 16384
    assert fm.token_macs(s, 2.5) == want
    # attention to 10 positions: 3 layers x 4 heads x (32 + 8 + 32) x 10
    assert fm.mla_attention_macs(s, 10) == 3 * 4 * 72 * 10
    assert fm.decode_token_flops(s, 10, 2.5) == 2 * (want + 8640)
    # held pairs: 4 choices x 2 layers x 30 of 120 pairs held
    assert fm.held_pairs_per_token(s, 30, 120) == 2.0
    att = fm.mla_decode_attention(s, [10, 1])
    assert att["flops"] == 2 * 3 * 4 * 72 * 11
    # bytes a layer: the latent rows once (40 wide), q (4 x 40), o (4 x 32)
    assert att["bytes"] == 3 * ((10 * 40 + 160 + 128) + (1 * 40 + 160 + 128)) * 2


# ------------------------------------------------------ the cell's correctness check
TINY_CONF = dict(TINY, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
                 kv_lora_rank=64, qk_rope_head_dim=16, qk_nope_head_dim=32,
                 v_head_dim=32, vocab_size=2048, expert_parallel={"rank": 0})


@pytest.fixture
def v2_root(tmp_path):
    """A checkout whose serve.v2lite.doc runs a DeepSeek-V2 a CPU serves in
    seconds, with the cell's published shape kept."""
    root = make_tiny_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "deepseek-v2-lite-ep8.json")
    with open(path) as f:
        conf = json.load(f)
    conf.update({k: v for k, v in TINY_CONF.items() if k != "expert_parallel"})
    conf["expert_parallel"]["rank"] = 0
    # this width's logits are narrower than the published model's: its own
    # limit, between the program's readings here and the float8 control's
    conf["check"]["max_logit_gap"] = 0.15
    write_json(path, conf)
    path = os.path.join(root, "bench", "traffic", "doc.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(rate_per_s=2.0, host={"slots": 4, "max_len": 96}, check={"requests": 2},
               drain_s=120)
    mix["prompt_tokens"] = {"dist": "lognormal", "median": 16, "sigma": 0.5,
                            "min": 8, "max": 32, "round_up_to": [16, 32]}
    mix["output_tokens"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 3, "max": 12}
    write_json(path, mix)
    return root


def test_program_correct_and_control_not(v2_root, interpret_kernels):
    """The program's served tokens pass the comparison; the float8 control
    put through the same comparison on the same sample does not, and reads
    above the bfloat16 witness."""
    c = harness.find_cell(harness.benchmark(v2_root), "serve.v2lite.doc", v2_root + "/bench")
    drv = harness.driver(c.config["kind"], v2_root + "/bench").Driver(c, 31, 3.0)
    drv.setup()
    run = harness.Run(cell="serve.v2lite.doc")
    drv.window(run)
    drv.free()
    assert run.sizes["Eh"] == 4 and drv.failed() == 0
    prog, ctl = drv.check(), drv.control_check()
    assert prog["correct"] is True, prog["numbers"]
    assert ctl["correct"] is False, ctl["numbers"]
    # the reference at the configuration's own bfloat16 reads below the control
    wit = drv.witness_check()
    assert wit["numbers"][0][1] < ctl["numbers"][0][1], (wit["numbers"], ctl["numbers"])


def test_dropped_expert_is_not_correct(v2_root, monkeypatch):
    """A program that leaves out one of its held experts serves tokens the
    reference does not."""
    from repro.models import moe

    orig = moe.held_experts_ffn

    def dropped(x2d, p, m):
        return orig(x2d, dict(p, wo=p["wo"].at[0].set(0)), m)

    monkeypatch.setattr(moe, "held_experts_ffn", dropped)
    res = run_cell("serve.v2lite.doc", 22, 3.0, False, require_chip=False,
                   root=v2_root)["result"]
    assert res["correct"] is False, res["checks"]
