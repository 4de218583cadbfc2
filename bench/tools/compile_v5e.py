"""Compile a serving cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/tools/compile_v5e.py serve.chat

Builds the cell's host programs at full size (each prompt bucket's prefill,
the undonated slot insert, the batched decode) from shapes alone, compiles
them for one chip of a described v5e:2x2, and prints each program's
``memory_analysis()``. What the chip's compiler refuses shows here first.
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, traffic
    from bench.drivers.serve_lm import model_config
    from repro.kernels.flash_attention import ops as attn_ops
    from repro.models.model import Model
    from repro.serving import kv_cache

    # JAX here sees the CPU, so the program's "auto" kernels would pick their
    # jnp reference; the chip runs the Pallas kernels, and so does this compile
    attn_ops._default_impl = lambda: "pallas"
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.find_cell(harness.benchmark(ROOT), name)
    cfg = model_config(cell.config)
    host = cell.traffic["host"]
    slots, max_len = host["slots"], host["max_len"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    model = Model(cfg)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    params = shaped(model.abstract_params())
    cache = shaped(jax.eval_shape(lambda: model.init_cache(slots, max_len)[0]))
    gib = 2.0 ** 30

    def report(label, compiled):
        m = compiled.memory_analysis()
        n_kernels = compiled.as_text().count("tpu_custom_call")
        print(f"{label}: arguments {m.argument_size_in_bytes / gib:.3f} GiB, "
              f"outputs {m.output_size_in_bytes / gib:.3f} GiB, "
              f"temp {m.temp_size_in_bytes / gib:.3f} GiB, "
              f"peak {m.peak_memory_in_bytes / gib:.3f} GiB, "
              f"tpu_custom_call x{n_kernels}", flush=True)

    print(f"{name}: {slots} slots x {max_len}, cache "
          f"{kv_cache.cache_bytes(cfg, slots, max_len) / 1e9:.3f} GB", flush=True)
    report("decode_step", jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one), cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)).compile())
    lens = sorted({r.prompt_len for r in traffic.sessions(cell.traffic, 0, 60.0)})
    for n in lens:
        tokens = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one)
        report(f"prefill S={n}", jax.jit(model.prefill).lower(params, {"tokens": tokens}).compile())
        seq = shaped(jax.eval_shape(model.prefill, model.abstract_params(),
                                    {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32)})[1])
        report(f"insert S={n}", jax.jit(kv_cache.insert_sequence).lower(
            cache, seq, jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile())


if __name__ == "__main__":
    for cell_name in sys.argv[1:]:
        main(cell_name)
