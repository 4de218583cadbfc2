"""The main path's kernels compile for a TPU v5e chip at real widths.

Nothing runs: each program is lowered and compiled for a described (not
attached) v5e device, which raises what the chip's compiler would raise —
block shapes off the (8, 128) tiling, too much VMEM, a program that does not
fit. ``tpu_custom_call`` in the compiled text shows the Pallas kernel is in
the program. Widths are qwen1.5-0.5b's (16 heads of 64), mamba2-2.7b's
(80 SSD heads of 64, state 128, chunk 256) and deepseek-v2-lite's (MLA: 16
heads, one 576-wide latent head in decode, q/k 192 and v 128 in prefill).
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import kernel as flash
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.ssd import kernel as ssd
from repro.launch.analysis import analyze_compiled
from repro.models.model import Model


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host; the persistent compilation
    cache is off meanwhile, since what it wrote could not be read back
    without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compiled_text(fn, *shapes, **jit_kw) -> str:
    return jax.jit(fn, **jit_kw).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("seq", [512, 37])
def test_flash_prefill_compiles_for_v5e(one_chip, seq):
    q = _spec(one_chip, (1, seq, 16, 64))
    text = _compiled_text(
        lambda q, k, v: flash.flash_attention_pallas(q, k, v, causal=True), q, q, q
    )
    assert "tpu_custom_call" in text


def test_vector_pos_decode_compiles_for_v5e(one_chip):
    q = _spec(one_chip, (8, 1, 16, 64))
    kv = _spec(one_chip, (8, 1024, 16, 64))
    pos = _spec(one_chip, (8,), jnp.int32)
    text = _compiled_text(flash.decode_attention_pallas, q, kv, kv, pos)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "minicpm3-4b"])
def test_decode_kernel_compiles_for_v5e_at_gqa_and_mla_widths(one_chip, arch):
    """GQA (qwen2-0.5b: 14 heads over 2) and MLA's latent decode
    (minicpm3-4b: 40 heads over one latent of 256 + 32 rope, values 256)."""
    cfg = get_config(arch)
    if cfg.mla is not None:
        kv, dk, dv = 1, cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim, cfg.mla.kv_lora_rank
    else:
        kv, dk, dv = cfg.n_kv_heads, cfg.hd, cfg.hd
    B, S = 32, 1536
    text = _compiled_text(
        flash.decode_attention_pallas,
        _spec(one_chip, (B, 1, cfg.n_heads, dk)),
        _spec(one_chip, (B, S, kv, dk)),
        _spec(one_chip, (B, S, kv, dv)),
        _spec(one_chip, (B,), jnp.int32),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_ssd_compiles_for_v5e_at_mamba2_widths(one_chip):
    full = get_config("mamba2-2.7b")
    s = full.ssm
    H, P, N = s.n_heads(full.d_model), s.head_dim, s.d_state
    assert (H, P, N, s.chunk) == (80, 64, 128, 256)
    S = 2 * s.chunk
    text = _compiled_text(
        lambda x, dt, A, b, c: ssd.ssd_pallas(
            x, dt, A, b, c, chunk=s.chunk, return_final_state=True
        ),
        _spec(one_chip, (1, S, H, P)),
        _spec(one_chip, (1, S, H), jnp.float32),
        _spec(one_chip, (H,), jnp.float32),
        _spec(one_chip, (1, S, 1, N)),
        _spec(one_chip, (1, S, 1, N)),
    )
    assert "tpu_custom_call" in text


def test_full_width_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The served decode program of qwen1.5-0.5b (8 slots of 1024 positions)
    with the attention dispatch steered to the Pallas kernel, as it is on a
    TPU backend."""
    monkeypatch.setattr(attn_ops, "_default_impl", lambda: "pallas")
    model = Model(get_config("qwen1.5-0.5b"))
    on_chip = lambda s: _spec(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(on_chip, model.abstract_params())
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: model.init_cache(8, 1024)[0]))
    compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params,
        _spec(one_chip, (8, 1), jnp.int32),
        cache,
        _spec(one_chip, (8,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # XLA's peak counts the arguments (0.93 GB of weights, 0.8 GB of cache)
    # as well as the temporaries, so analyze_compiled reports it as resident
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes >= mem.argument_size_in_bytes > 1.5e9
    resident = analyze_compiled(compiled, n_chips=1)["memory"]["resident_bytes"]
    assert resident == mem.peak_memory_in_bytes


def _computations(text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line.strip())
    return comps


def _calls(line):
    return re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)


def _holds(comps, comp, needle, seen=()):
    """Whether computation `comp`, or one it calls, has a line with `needle`."""
    return any(needle in line or any(c not in seen and _holds(comps, c, needle, seen + (comp,))
                                     for c in _calls(line))
               for line in comps[comp])


def test_served_decode_step_reads_cache_in_one_kernel(one_chip, monkeypatch):
    """The served qwen1.5-0.5b decode step at serve.chat's 32 slots x 1536
    positions: one decode kernel per layer body for every slot (no loop over
    slots), fed K/V with no copy into its layout, in no more temporary
    memory than the per-slot kernel needed (6.04 GB)."""
    monkeypatch.setattr(attn_ops, "_default_impl", lambda: "pallas")
    cfg = get_config("qwen1.5-0.5b")
    model = Model(cfg)
    on_chip = lambda s: _spec(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(on_chip, model.abstract_params())
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: model.init_cache(32, 1536)[0]))
    compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params,
        _spec(one_chip, (32, 1), jnp.int32),
        cache,
        _spec(one_chip, (32,), jnp.int32),
    ).compile()
    comps = _computations(compiled.as_text())
    kernel = 'custom_call_target="tpu_custom_call"'
    sites = [(c, line) for c, lines in comps.items() for line in lines if kernel in line]
    assert len(sites) == 1, [line[:120] for _, line in sites]
    # the one while is the scan over layers, and its body holds the kernel
    whiles = [line for lines in comps.values() for line in lines if " while(" in line]
    assert len(whiles) == 1, [w[:120] for w in whiles]
    body = re.search(r"body=%([\w.\-]+)", whiles[0]).group(1)
    assert _holds(comps, body, kernel)

    comp, call = sites[0]
    defs = {re.match(r"(?:ROOT )?%(\S+) = ", line).group(1): line for line in comps[comp]}
    operands = re.sub(r"/\*.*?\*/", "", re.search(r"custom-call\(([^)]*)\)", call).group(1))
    operands = operands.split(", ")
    for name in operands[-2:]:                      # K and V come last
        line = defs[name.lstrip("%")]
        while " bitcast(" in line:
            line = defs[re.search(r" bitcast\(%([\w.\-]+)\)", line).group(1)]
        assert not re.match(r"(ROOT )?%copy", line), line[:160]
        assert " copy(" not in line and " copy-done(" not in line, line[:160]

    assert compiled.memory_analysis().temp_size_in_bytes <= 6.04e9


def test_deepseek_v2_lite_share_compiles_for_v5e(one_chip, monkeypatch):
    """deepseek-v2-lite at published widths and one chip's share of an
    8-way expert-parallel deployment (8 of 64 routed experts held), served
    at 16 slots x 8704: the decode step runs the latent decode kernel (one
    head, dk 576, dv 512) in each of its two layer stacks (the dense layer,
    the expert layers) and fits the chip's 16 GiB with the weights and the
    cache; prefill at 8192 runs flash attention at q/k 192 and v 128."""
    monkeypatch.setattr(attn_ops, "_default_impl", lambda: "pallas")
    cfg = get_config("deepseek-v2-lite")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=8))
    model = Model(cfg)
    on_chip = lambda s: _spec(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(on_chip, model.abstract_params())
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: model.init_cache(16, 8704)[0]))

    def decode_step(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos, with_stats=True)

    compiled = jax.jit(decode_step, donate_argnums=(2,)).lower(
        params, _spec(one_chip, (16, 1), jnp.int32), cache,
        _spec(one_chip, (16,), jnp.int32),
    ).compile()
    kernel = 'custom_call_target="tpu_custom_call"'
    assert compiled.as_text().count(kernel) == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 10e9          # 6.22 GB weights + 4.33 GB cache
    assert mem.peak_memory_in_bytes < 0.95 * 16 * 2**30

    def prefill(params, batch):
        return model.prefill(params, batch, with_stats=True)

    text = _compiled_text(prefill, params, {"tokens": _spec(one_chip, (1, 8192), jnp.int32)})
    assert text.count(kernel) == 2
