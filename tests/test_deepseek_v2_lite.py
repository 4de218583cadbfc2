"""deepseek-v2-lite at the REDUCED size on the CPU, against the plain
reference (``bench/reference/deepseek_v2.py``) on the same seeded weights:
served prefill and batched decode through ``ModelHost``, the chip's share of
the routed experts, dropless dispatch, YaRN, and the MoE and MLA paths of
the configs that share the code."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v2 as ref
from repro.configs import get_config, get_reduced
from repro.core.metrics import MetricsRegistry
from repro.models import layers, mla, moe
from repro.models.model import Model
from repro.serving.fabric import ModelHost

# both sides float32, the program's attention and experts in another order
# than the reference's (absorbed latent decode, masked dense experts): what
# differs is float32 summation, ~1e-6 of the largest logit; 1e-4 leaves room
# and fails on any change of the mathematics (a wrong rope pair, scale or
# routing moves logits by more than 1e-2)
REL_TOL = 1e-4


def hp_of(cfg):
    """The published keys the reference reads, for a program config."""
    a, m, y = cfg.mla, cfg.moe, cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": m.d_ff_expert, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.first_dense_layers,
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": a.kv_lora_rank,
        "qk_rope_head_dim": a.qk_rope_dim, "qk_nope_head_dim": a.qk_nope_dim,
        "v_head_dim": a.v_head_dim, "n_shared_experts": m.n_shared_experts,
        "router_width": m.n_experts, "n_routed_experts": m.held,
        "num_experts_per_tok": m.top_k, "vocab_size": cfg.vocab,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "routed_scaling_factor": 1,
        "rope_scaling": {"factor": y.factor, "original_max_position_embeddings":
                         y.original_max_position, "beta_fast": y.beta_fast,
                         "beta_slow": y.beta_slow, "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim, "type": "yarn"},
        "expert_parallel": {"rank": m.first_held // m.held},
    }


@pytest.fixture(scope="module")
def reduced():
    cfg = get_reduced("deepseek-v2-lite").with_(dtype="float32")
    hp = hp_of(cfg)
    params = ref.init_params(7, hp, dtype=jnp.float32)
    model = Model(cfg)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == want
    return cfg, hp, model, params


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= REL_TOL * np.max(np.abs(want))


def test_served_prefill_and_batched_decode_match_reference(reduced):
    """Three sessions prefilled into slots of one host, decoded in batched
    steps to different positions; then every live slot's decode logits and
    each prefill's last logits against the reference's full forward."""
    cfg, hp, model, params = reduced
    rng = np.random.default_rng(11)
    with jax.default_matmul_precision("highest"):
        host = ModelHost(model, params, max_len=64, max_sessions=4,
                         metrics=MetricsRegistry())
        hist, plen = {}, {}
        for name, n in (("a", 5), ("b", 17), ("c", 9)):
            plen[name] = n
            prompt = rng.integers(0, cfg.vocab, n).astype(np.int32)
            logits = host._prefill(params, {"tokens": prompt[None]})[0]
            _close(logits[0], ref.logits(params, hp, prompt)[-1])
            hist[name] = list(prompt) + [host.prefill(name, prompt)]
        for name, steps in (("a", 3), ("b", 1), ("c", 2)):
            for _ in range(steps):
                hist[name].append(host.decode(name, hist[name])[0])
        with host._lock:
            logits = host._decode(params, jnp.asarray(host.slot_last[:, None]), host.cache,
                                  jnp.asarray(host.slot_pos))[0]
            slots = {n: host.sessions[n].slot for n in hist}
    for name, h in hist.items():
        want = ref.logits(params, hp, np.asarray(h, np.int32))
        _close(logits[slots[name]], want[-1])
        # every token served so far was the reference's argmax
        n = plen[name]
        assert h[n:] == [int(t) for t in np.argmax(want[n - 1:-1], axis=-1)]


def test_routing_counters_read_back_with_tokens(reduced):
    """The host's counters: rows = held experts x step tokens x expert
    layers, idle slots included; all pairs = served tokens x top_k x expert
    layers and held pairs as the router gives them, both over the sessions
    served only; latent positions read per decode step summed over every
    slot."""
    cfg, hp, model, params = reduced
    metrics = MetricsRegistry()
    host = ModelHost(model, params, max_len=32, max_sessions=2, metrics=metrics)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab, 6).astype(np.int32)
    host.prefill("s", prompt)
    c = metrics.snapshot()["counters"]
    m, Lm = cfg.moe, cfg.n_layers - cfg.first_dense_layers
    assert c["serving.moe_rows.prefill"] == m.held * 6 * Lm
    assert c["serving.moe_assign_all.prefill"] == 6 * m.top_k * Lm
    assert 0 < c["serving.moe_assign_held.prefill"] <= 6 * m.top_k * Lm
    host.decode("s", [])
    host.decode("s", [])
    c = metrics.snapshot()["counters"]
    # two batched steps over both slots (one idle at position 0): the rows
    # count the idle slot, the pairs only the session served
    assert c["serving.moe_rows.decode"] == 2 * m.held * 2 * Lm
    assert c["serving.moe_assign_all.decode"] == 2 * 1 * m.top_k * Lm
    assert 0 <= c["serving.moe_assign_held.decode"] <= 2 * 1 * m.top_k * Lm
    assert c["serving.mla_positions_read.decode"] == (7 + 1) + (8 + 1)


def _expert_layer_input(cfg, T, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, cfg.d_model), jnp.float32)


def test_expert_shares_add_up_to_the_uncut_layer(reduced):
    """Two chips' shares of 8 experts, 4 each: their expert layers' outputs,
    with the shared experts counted once, add up to the uncut reference
    layer holding all 8."""
    cfg, hp, model, _ = reduced
    full_hp = dict(hp, n_routed_experts=8, expert_parallel={"rank": 0})
    full = ref.init_params(8, full_hp, dtype=jnp.float32)["layers"]["ffn"]
    f = jax.tree.map(lambda a: a[0], full)            # one expert layer
    x = _expert_layer_input(cfg, 24, 1)
    parts, shared = [], None
    for rank in (0, 1):
        m = dataclasses.replace(cfg.moe, first_held=4 * rank, n_held=4)
        p = {"router": f["router"], "shared": f["shared"],
             **{n: f[n][4 * rank: 4 * rank + 4] for n in ("wi", "wg", "wo")}}
        y, _, counts = moe.moe_layer(x, p, cfg.with_(moe=m))
        parts.append(y[0])
        assert int(counts[1]) == 4 * 24                # held experts x tokens
    shared = moe.layers.swiglu(x, f["shared"])[0]
    with jax.default_matmul_precision("highest"):
        want = ref._experts(f, x[0], full_hp, False)
    _close(parts[0] + parts[1] - shared, want)


def test_dropless_when_every_token_picks_one_expert(reduced):
    """Routing forced onto expert 1 for every token: the dropless layer
    gives the reference's answer; a capacity of 1.25 x T x k / E would keep
    only a few of those tokens."""
    cfg, hp, _, params = reduced
    f = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
    f = dict(f, router=f["router"].at[:, 1].set(0.0))
    x = _expert_layer_input(cfg, 32, 2)
    # a constant feature carries expert 1's score far above the others'
    x = x.at[..., 0].set(10.0)
    f["router"] = f["router"].at[0, 1].set(10.0)
    topi = jax.lax.top_k(jnp.einsum("td,de->te", x[0], f["router"]), cfg.moe.top_k)[1]
    assert bool(jnp.all(topi[:, 0] == 1))
    y, _, counts = moe.moe_layer(x, f, cfg)
    assert bool(jnp.all(counts[0] >= 1))              # every token's choice of 1 kept
    with jax.default_matmul_precision("highest"):
        want = ref._experts(f, x[0], hp, False)
    _close(y[0], want)


def test_yarn_frequencies_and_softmax_scale():
    """DeepSeek-V2-Lite's YaRN, written out: 32 inverse frequencies over
    the 64 rope dims, interpolated by 40 below the beta_slow dim and kept
    above beta_fast's; softmax scale 192^-0.5 * mscale^2 with mscale =
    0.1 * 0.707 * ln 40 + 1 = 1.2608; cos and sin unscaled."""
    cfg = get_config("deepseek-v2-lite")
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    want = []
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    got = layers.rope_frequencies(dim, base, cfg.rope_scaling)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert abs(mscale - 1.2608) < 1e-4
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert layers.rope_cos_scale(cfg.rope_scaling) == 1.0


def test_qwen2_moe_keeps_its_gated_shared_expert(key):
    """qwen2-moe-a2.7b: the shared expert's output still passes its sigmoid
    gate, beside the capacity-dispatched routed experts."""
    cfg = get_reduced("qwen2-moe-a2.7b").with_(dtype="float32")
    assert cfg.moe.shared_gate and not cfg.moe.dropless
    p, _ = moe.init_moe(key, cfg)
    assert "shared_gate" in p
    x = jax.random.normal(key, (1, 12, cfg.d_model), jnp.float32)
    y, _ = moe.moe_ffn(x, p, cfg)
    no_shared = dict(p, shared=dict(p["shared"], wo=jnp.zeros_like(p["shared"]["wo"])))
    routed, _ = moe.moe_ffn(x, no_shared, cfg)
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dg->bsg", x, p["shared_gate"]))
    np.testing.assert_allclose(np.asarray(y - routed),
                               np.asarray(gate * layers.swiglu(x, p["shared"])),
                               rtol=1e-5, atol=1e-5)


def test_minicpm3_keeps_its_query_low_rank_step(key):
    """minicpm3-4b: queries through wdq, q_norm and wuq (no direct wq), and
    the latent decode through the cache still gives the forward's logits."""
    cfg = get_reduced("minicpm3-4b").with_(dtype="float32")
    model = Model(cfg)
    params = model.init(key)
    attn = params["layers"]["attn"]
    assert {"wdq", "q_norm", "wuq"} <= set(attn) and "wq" not in attn
    tokens = np.random.default_rng(5).integers(0, cfg.vocab, (1, 10)).astype(np.int32)
    h, _ = model.forward(params, {"tokens": tokens})
    full = np.asarray(model._logits(params, h))[0]
    _, cache = model.prefill(params, {"tokens": tokens[:, :8]})
    cache0, _ = model.init_cache(1, 12)
    cache = jax.tree.map(lambda z, c: z.at[:, :, : c.shape[2]].set(c), cache0, cache)
    logits, _ = model.decode_step(params, jnp.asarray(tokens[:, 8:9]), cache, jnp.int32(8))
    np.testing.assert_allclose(np.asarray(logits)[0], full[8], rtol=2e-4, atol=2e-4)
