"""Driver for DeepSeek-V2 served through the fabric at one chip's share of
its routed experts (``kind: serve_deepseek_v2``).

The deployment, the window, the timing, the end-to-end numbers and the
notes are ``serve_lm``'s (one ``FunctionService``, one jit-capable endpoint
standing for one chip, ``serve_model`` with the mix's slots and
``max_len``); this driver gives them the program's configuration of the
published keys, the weights and the check of ``bench/reference/deepseek_v2``
and the sizes its per-layer metrics read.
"""
from __future__ import annotations

import functools
import gc
from typing import Dict

import jax

from bench import flops_mla_moe
from bench import traffic as gen
from bench.drivers import serve_lm
from bench.harness import Run, record_futures
from bench.reference import deepseek_v2


def model_config(conf: Dict):
    """The program's ModelConfig for the published keys in `conf`, holding
    the configuration's share of the routed experts."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

    rs = conf["rope_scaling"]
    return ModelConfig(
        name=conf["name"],
        family="moe",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        rope_scaling=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        ),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        first_dense_layers=int(conf["first_k_dense_replace"]),
        mla=MLAConfig(
            q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_dim=conf["qk_nope_head_dim"], qk_rope_dim=conf["qk_rope_head_dim"],
            v_head_dim=conf["v_head_dim"], rope_interleaved=True,
        ),
        moe=MoEConfig(
            n_experts=conf["router_width"], top_k=conf["num_experts_per_tok"],
            d_ff_expert=conf["moe_intermediate_size"],
            n_shared_experts=conf["n_shared_experts"],
            d_ff_shared=conf["moe_intermediate_size"] * conf["n_shared_experts"],
            norm_topk_prob=bool(conf["norm_topk_prob"]), shared_gate=False, dropless=True,
            first_held=deepseek_v2.first_held(conf), n_held=conf["n_routed_experts"],
        ),
        dtype=conf["torch_dtype"],
    )


class Driver(serve_lm.Driver):
    def __init__(self, cell, seed: int, seconds: float):
        super().__init__(cell, seed, seconds)
        conf = self.conf
        if conf["routed_scaling_factor"] != 1 or conf["scoring_func"] != "softmax" \
                or conf["topk_method"] != "greedy":
            raise ValueError("the program routes by softmax scores, greedy top-k, "
                             "with routed_scaling_factor 1")
        # a program without latent attention under YaRN, dropless routing and
        # a share of the experts fails here, before the weights are made
        try:
            self.cfg = model_config(conf)
        except (ImportError, TypeError) as exc:
            raise RuntimeError(f"this program cannot serve {conf['name']}: its configs lack "
                               f"what the configuration needs ({exc})") from exc

    def setup(self) -> None:
        from repro.core import FunctionService
        from repro.core.containers import ContainerSpec
        from repro.models.model import Model
        from repro.serving.fabric import serve_model

        conf = self.conf
        # a driver set up before this one in the same process (bench/tools)
        # must have let go of its weights: two sets do not fit with a cache
        gc.collect()
        self.params = deepseek_v2.init_params(self.seed, conf, dtype=self.cfg.dtype)
        model = Model(self.cfg)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if want != got:
            raise ValueError(f"weights' layout differs from the program's: {got} vs {want}")
        vocab = conf["vocab_size"]

        self.service = FunctionService()
        jit = ContainerSpec(name="jit", capabilities={"cpu", "jit"},
                            min_workers=0, max_workers=self.slots)
        self.endpoint = self.service.make_endpoint("chip0", n_executors=1, containers=[jit])
        dep = conf.get("deployment", {}).get("serve_model", {})
        self.client = serve_model(
            self.service, model, self.params, name=self.cfg.name,
            max_len=self.max_len, max_sessions=self.slots,
            batching=dep.get("batching", True), window_s=dep.get("window_s", 0.003),
        )
        kinds = {fid: which for which, fid in self.client.fids.items()}

        def meta_of(kind, doc):
            return {"n_tokens": len(doc.get("tokens", ()))}

        record_futures(self.service, kinds, meta_of, self.tasks)
        # every prompt length the window sends: prefill and slot insert per
        # length, the batched decode, the host's small programs
        for n in sorted({r.prompt_len for r in self.requests}):
            prompt = gen.token_ids(self.seed, 10**6 + n, n, vocab)
            with self.client.session(prompt, timeout=1200) as s:
                s.step(timeout=1200)

    def window(self, run: Run) -> None:
        run.sizes = flops_mla_moe.sizes(self.conf)
        super().window(run)

    def check(self) -> Dict:
        """The program's served tokens against the reference."""
        return self._compare(deepseek_v2.served_gaps)

    def control_check(self) -> Dict:
        """The same comparison with the float8 reference in the program's place."""
        return self._compare(deepseek_v2.control_gaps)

    def witness_check(self) -> Dict:
        """The same comparison with the reference at bfloat16, the
        configuration's precision, in the program's place: the gaps that
        rounding alone gives, beside the program's."""
        return self._compare(functools.partial(deepseek_v2.control_gaps, rounding="bfloat16"))
