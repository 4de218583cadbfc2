"""Model assembly: embedding + scanned layer stack + head, per family.

One :class:`Model` serves all 10 assigned architectures. Stacked-per-layer
parameters + ``lax.scan`` keep the HLO O(1) in depth (a 95-layer dry-run
compiles in the same time as a 2-layer one); ``jax.checkpoint`` around the
scan body implements the remat policy.

API:
    init(key) / init_with_specs(key) / specs() / abstract_params()
    loss(params, batch)                         -> (scalar, metrics)
    forward(params, batch)                      -> (logits, aux)
    prefill(params, batch)                      -> (last_logits, cache)
    decode_step(params, token, cache, pos)      -> (logits, new_cache)
    init_cache(batch, cache_len)                -> (cache, logical_specs)

``prefill`` and ``decode_step`` take ``with_stats=True`` to return a third
value, the step's routing counts summed over its expert layers, int32
(2 + B,): expert rows computed (padding included), (token, choice) pairs of
each batch row (the same for every row), then each row's pairs on the
experts held here; zeros for a model without routed experts.

A decoder (dense, moe, vlm) keeps its layers in scanned stacks by name:
``params["layers"]``, after ``params["dense_layers"]`` for a moe config
with ``first_dense_layers``. Its cache is keyed the same way.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple


import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import partition
from . import blocks, layers, mamba2

AUX_COEF = 0.01


def _remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    return jax.checkpoint(fn, policy=policies[cfg.remat_policy])


def _stack_init(init_fn, key, n: int):
    """vmap an init over n layer keys -> stacked params; specs get a leading
    'layers' logical axis."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    captured = {}

    def probe(k):
        p, s = init_fn(k)
        captured["s"] = s
        return p

    jax.eval_shape(probe, keys[0])  # abstract: captures static specs only
    specs = jax.tree.map(
        lambda s: ("layers", *s), captured["s"], is_leaf=lambda x: isinstance(x, tuple)
    )
    return params, specs


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ================================================================ init
    def init_with_specs(self, key) -> Tuple[Dict, Dict]:
        cfg = self.cfg
        dt = layers.dtype_of(cfg)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {}
        specs: Dict[str, Any] = {}

        params["embed"], specs["embed"] = layers.init_embedding(keys[0], cfg.vocab, cfg.d_model, dt)
        if not cfg.tie_embeddings:
            params["unembed"], specs["unembed"] = layers.init_unembed(
                keys[1], cfg.vocab, cfg.d_model, dt
            )
        params["final_norm"], specs["final_norm"] = (
            layers.init_layernorm(cfg.d_model)
            if cfg.family == "encdec"
            else layers.init_rmsnorm(cfg.d_model)
        )

        if cfg.family in ("dense", "moe", "vlm"):
            for name, n, dense in self._decoder_stacks():
                params[name], specs[name] = _stack_init(
                    lambda k, dense=dense: blocks.init_decoder_layer(k, cfg, dense),
                    keys[4] if dense else keys[2], n,
                )
            if cfg.family == "vlm":
                params["patch_proj"] = layers.dense_init(
                    keys[3], (cfg.d_model, cfg.d_model), cfg.d_model, dt
                )
                specs["patch_proj"] = ("embed", "mlp")
        elif cfg.family == "ssm":
            params["layers"], specs["layers"] = _stack_init(
                lambda k: blocks.init_ssm_layer(k, cfg), keys[2], cfg.n_layers
            )
        elif cfg.family == "hybrid":
            G, PG = self._hybrid_groups()
            flat, flat_specs = _stack_init(
                lambda k: blocks.init_ssm_layer(k, cfg), keys[2], cfg.n_layers
            )
            params["layers"] = jax.tree.map(
                lambda x: x.reshape(G, PG, *x.shape[1:]), flat
            )
            # params are (G, PG, ...): prepend a second "layers" name
            specs["layers"] = jax.tree.map(
                lambda s: ("layers", *s), flat_specs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            params["shared"], specs["shared"] = blocks.init_decoder_layer(keys[3], cfg)
        elif cfg.family == "encdec":
            params["enc_layers"], specs["enc_layers"] = _stack_init(
                lambda k: blocks.init_encoder_layer(k, cfg), keys[2], cfg.n_enc_layers
            )
            params["layers"], specs["layers"] = _stack_init(
                lambda k: blocks.init_cross_decoder_layer(k, cfg), keys[3], cfg.n_layers
            )
            params["enc_norm"], specs["enc_norm"] = layers.init_layernorm(cfg.d_model)
        else:
            raise ValueError(cfg.family)
        return params, specs

    def init(self, key) -> Dict:
        return self.init_with_specs(key)[0]

    def specs(self) -> Dict:
        captured: Dict[str, Any] = {}

        def f(key):
            p, s = self.init_with_specs(key)
            captured["specs"] = s
            return p

        jax.eval_shape(f, jax.random.PRNGKey(0))
        return captured["specs"]

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def _decoder_stacks(self) -> List[Tuple[str, int, bool]]:
        """(params key, layers, dense MLP) of each scanned decoder stack: a
        moe config's leading dense layers first, then the rest."""
        cfg = self.cfg
        n = cfg.first_dense_layers if cfg.family == "moe" else 0
        rest = [("layers", cfg.n_layers - n, False)]
        return [("dense_layers", n, True)] + rest if n else rest

    def _hybrid_groups(self) -> Tuple[int, int]:
        cfg = self.cfg
        PG = cfg.shared_attn_every
        assert cfg.n_layers % PG == 0, (cfg.n_layers, PG)
        return cfg.n_layers // PG, PG

    # ============================================================ embedding
    def _embed_inputs(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        tokens = batch["tokens"]
        h = layers.embed(tokens, params["embed"])
        if cfg.family == "vlm":
            patches = jnp.einsum("bpd,de->bpe", batch["patches"].astype(h.dtype),
                                 params["patch_proj"])
            h = jnp.concatenate([patches, h], axis=1)
        if cfg.family == "encdec":
            pos = layers.sinusoidal_positions(h.shape[1], cfg.d_model).astype(h.dtype)
            h = h + pos[None]
        return partition.shard_act(h, "batch", "seq", None)

    # ============================================================== forward
    def forward(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-sequence forward. Returns (hidden_states, aux_loss)."""
        cfg = self.cfg
        h = self._embed_inputs(params, batch)
        S = h.shape[1]
        positions = jnp.arange(S)

        if cfg.family in ("dense", "moe", "vlm"):
            aux = jnp.float32(0.0)
            for name, _, dense in self._decoder_stacks():
                def body(carry, lp, dense=dense):
                    hh, aux = carry
                    hh, a, _, _ = blocks.decoder_layer(lp, hh, cfg, positions, dense)
                    return (hh, aux + a), None

                (h, aux), _ = self._scan(body, (h, aux), params[name])
        elif cfg.family == "ssm":
            def body(carry, lp):
                hh, _ = blocks.ssm_layer(lp, carry[0], cfg)
                return (hh, carry[1]), None

            (h, _), _ = self._scan(body, (h, jnp.float32(0.0)), params["layers"])
            aux = jnp.float32(0.0)
        elif cfg.family == "hybrid":
            shared = params["shared"]

            def group(carry, glp):
                hh, aux = carry
                hh, a, _, _ = blocks.decoder_layer(shared, hh, cfg, positions)

                def inner(c, lp):
                    h2, _ = blocks.ssm_layer(lp, c, cfg)
                    return h2, None

                hh, _ = self._scan(inner, hh, glp)
                return (hh, aux + a), None

            (h, aux), _ = self._scan(group, (h, jnp.float32(0.0)), params["layers"])
        elif cfg.family == "encdec":
            enc = self._encode(params, batch)

            def body(carry, lp):
                hh, _ = blocks.cross_decoder_layer(lp, carry[0], enc, cfg)
                return (hh, carry[1]), None

            (h, _), _ = self._scan(body, (h, jnp.float32(0.0)), params["layers"])
            aux = jnp.float32(0.0)
        else:
            raise ValueError(cfg.family)

        if cfg.family == "encdec":
            h = layers.layernorm(h, params["final_norm"], cfg.norm_eps)
        else:
            h = layers.rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, aux

    def _scan(self, body, carry, stacked):
        if self.cfg.scan_layers:
            return jax.lax.scan(_remat(body, self.cfg), carry, stacked)
        n = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda x: x[i], stacked)
            carry, _ = _remat(body, self.cfg)(carry, lp)
        return carry, None

    def _scan_ys(self, body, carry, xs):
        """scan that also stacks per-layer outputs; honours scan_layers=False
        (unrolled — used by the dry-run so XLA cost analysis sees every layer
        instead of a single while-loop body)."""
        if self.cfg.scan_layers:
            return jax.lax.scan(_remat(body, self.cfg), carry, xs)
        n = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(n):
            xi = jax.tree.map(lambda x: x[i], xs)
            carry, y = _remat(body, self.cfg)(carry, xi)
            ys.append(y)
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls, axis=0), *ys)
        return carry, stacked

    def _encode(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        frames = batch["frames"].astype(layers.dtype_of(cfg))
        pos = layers.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(frames.dtype)
        h = frames + pos[None]

        def body(carry, lp):
            return blocks.encoder_layer(lp, carry, cfg), None

        h, _ = self._scan(body, h, params["enc_layers"])
        return layers.layernorm(h, params["enc_norm"], cfg.norm_eps)

    def _logits(self, params, h: jnp.ndarray) -> jnp.ndarray:
        unembed = params.get("unembed")
        logits = layers.logits_from(h, unembed, params["embed"])
        return partition.shard_act(logits, "batch", "seq", "vocab")

    # ================================================================= loss
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        h, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        if cfg.family == "vlm":
            P = cfg.n_patches
            St = tokens.shape[1]
            h_lm = jax.lax.dynamic_slice_in_dim(h, P - 1, St, axis=1)
            targets = tokens
        else:
            h_lm = h[:, :-1]
            targets = tokens[:, 1:]
        logits = self._logits(params, h_lm)
        mask = batch.get("loss_mask")
        if mask is not None and cfg.family != "vlm":
            mask = mask[:, 1:]
        ce = layers.cross_entropy_loss(logits, targets, mask)
        total = ce + AUX_COEF * aux
        return total, {"ce": ce, "aux": aux, "loss": total}

    # ============================================================== prefill
    def prefill(self, params, batch, with_stats: bool = False):
        """Run the full prompt, return (last-position logits (B, V), cache),
        and the routing counts where `with_stats`."""
        cfg = self.cfg
        h = self._embed_inputs(params, batch)
        S = h.shape[1]
        positions = jnp.arange(S)
        counts = self._no_counts(h.shape[0] * S)

        if cfg.family in ("dense", "moe", "vlm"):
            cache = {}
            for name, _, dense in self._decoder_stacks():
                def body(hh, lp, dense=dense):
                    hh, _, kv, c = blocks.decoder_layer(lp, hh, cfg, positions, dense)
                    return hh, (self._pack_kv(kv), c)

                h, (cache[name], c) = self._scan_prefill(body, h, params[name])
                counts = jax.tree.map(lambda a, b: a + b.sum(axis=0), counts, c)
        elif cfg.family == "ssm":
            def body(hh, lp):
                hh, state = blocks.ssm_layer(lp, hh, cfg, return_state=True)
                return hh, state

            h, cache = self._scan_prefill(body, h, params["layers"])
        elif cfg.family == "hybrid":
            shared = params["shared"]

            def group(hh, glp):
                hh, _, kv, _ = blocks.decoder_layer(shared, hh, cfg, positions)

                def inner(c, lp):
                    c, state = blocks.ssm_layer(lp, c, cfg, return_state=True)
                    return c, state

                hh, mstates = self._scan_ys(inner, hh, glp)
                return hh, {"attn": self._pack_kv(kv), "mamba": mstates}

            h, cache = self._scan_prefill(group, h, params["layers"])
        elif cfg.family == "encdec":
            enc = self._encode(params, batch)

            def body(hh, lp):
                hh, (self_kv, cross_kv) = blocks.cross_decoder_layer(lp, hh, enc, cfg)
                sk, sv = self_kv
                ck, cv = cross_kv
                return hh, {"k": sk, "v": sv, "cross_k": ck, "cross_v": cv}

            h, cache = self._scan_prefill(body, h, params["layers"])
        else:
            raise ValueError(cfg.family)

        norm = layers.layernorm if cfg.family == "encdec" else layers.rmsnorm
        h = norm(h, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, h[:, -1:])[:, 0]
        if with_stats:
            return logits, cache, self._routing_stats(counts, S)
        return logits, cache

    def _pack_kv(self, kv):
        if self.cfg.mla is not None:
            return {"latent": kv}
        return {"k": kv[0], "v": kv[1]}

    @staticmethod
    def _no_counts(tokens: int):
        return jnp.zeros(tokens, jnp.int32), jnp.int32(0)

    def _routing_stats(self, counts, S: int) -> jnp.ndarray:
        """A step's routing counts (held choices per token, rows computed),
        summed over its expert layers, as ``with_stats`` returns them."""
        held, rows = counts
        cfg = self.cfg
        routed = sum(n for _, n, dense in self._decoder_stacks() if not dense) \
            if cfg.family == "moe" else 0
        pairs = S * cfg.moe.top_k * routed if routed else 0
        return jnp.concatenate([jnp.stack([rows, jnp.int32(pairs)]),
                                held.reshape(-1, S).sum(axis=1)])

    def _scan_prefill(self, body, h, stacked):
        return self._scan_ys(body, h, stacked)

    # =============================================================== decode
    def decode_step(self, params, token: jnp.ndarray, cache: Any, pos: jnp.ndarray,
                    with_stats: bool = False):
        """token: (B, 1) int32; pos: scalar int32 (write position). Returns
        (logits (B, V), new_cache), and the routing counts where `with_stats`."""
        cfg = self.cfg
        counts = self._no_counts(token.shape[0])
        h = layers.embed(token, params["embed"])
        if cfg.family == "encdec":
            pe = layers.sinusoidal_positions(cache_len_of(cache), cfg.d_model)
            if pos.ndim == 1:
                h = h + jnp.take(pe, pos, axis=0)[:, None].astype(h.dtype)
            else:
                h = h + jax.lax.dynamic_slice_in_dim(pe, pos, 1, axis=0)[None].astype(h.dtype)
        h = partition.shard_act(h, "batch", "seq", None)

        if cfg.family in ("dense", "moe", "vlm"):
            new_cache = {}
            for name, _, dense in self._decoder_stacks():
                def body(hh, xs, dense=dense):
                    lp, lc = xs
                    hh, nc, c = blocks.decoder_layer_decode(lp, hh, lc, pos, cfg, dense)
                    return hh, (nc, c)

                h, (new_cache[name], c) = self._scan_ys(body, h, (params[name], cache[name]))
                counts = jax.tree.map(lambda a, b: a + b.sum(axis=0), counts, c)
        elif cfg.family == "ssm":
            def body(hh, xs):
                lp, st = xs
                hh, ns = blocks.ssm_layer_decode(lp, hh, st, cfg)
                return hh, ns

            h, new_cache = self._scan_ys(body, h, (params["layers"], cache))
        elif cfg.family == "hybrid":
            shared = params["shared"]

            def group(hh, xs):
                glp, gc = xs
                hh, attn_nc, _ = blocks.decoder_layer_decode(shared, hh, gc["attn"], pos, cfg)

                def inner(c, ys):
                    lp, st = ys
                    c, ns = blocks.ssm_layer_decode(lp, c, st, cfg)
                    return c, ns

                hh, mamba_nc = self._scan_ys(inner, hh, (glp, gc["mamba"]))
                return hh, {"attn": attn_nc, "mamba": mamba_nc}

            h, new_cache = self._scan_ys(group, h, (params["layers"], cache))
        elif cfg.family == "encdec":
            def body(hh, xs):
                lp, lc = xs
                hh, nc = blocks.cross_decoder_layer_decode(lp, hh, lc, pos, cfg)
                return hh, nc

            h, new_cache = self._scan_ys(body, h, (params["layers"], cache))
        else:
            raise ValueError(cfg.family)

        norm = layers.layernorm if cfg.family == "encdec" else layers.rmsnorm
        h = norm(h, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, h)[:, 0]
        if with_stats:
            return logits, new_cache, self._routing_stats(counts, 1)
        return logits, new_cache

    # ================================================================ cache
    def init_cache(self, batch: int, cache_len: int) -> Tuple[Any, Any]:
        """Zero decode cache + logical axis specs (stacked over layers)."""
        cfg = self.cfg

        def stack(cache, specs, n):
            c = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), cache)
            s = jax.tree.map(lambda t: ("layers", *t), specs,
                             is_leaf=lambda x: isinstance(x, tuple))
            return c, s

        if cfg.family in ("dense", "moe", "vlm"):
            # cache_len counts TOTAL sequence slots (patches included for vlm)
            c, s = blocks.init_decoder_cache(cfg, batch, cache_len)
            stacks = {name: stack(c, s, n) for name, n, _ in self._decoder_stacks()}
            return ({k: v[0] for k, v in stacks.items()},
                    {k: v[1] for k, v in stacks.items()})
        if cfg.family == "ssm":
            c, s = mamba2.init_decode_state(cfg, batch)
            c = {"conv": c["conv"], "ssm": c["ssm"]}
            return stack(c, s, cfg.n_layers)
        if cfg.family == "hybrid":
            G, PG = self._hybrid_groups()
            ac, asp = blocks.init_decoder_cache(cfg, batch, cache_len)
            mc, msp = mamba2.init_decode_state(cfg, batch)
            mc_stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (PG, *x.shape)), mc)
            msp = jax.tree.map(lambda t: ("layers", *t), msp,
                               is_leaf=lambda x: isinstance(x, tuple))
            cache = {"attn": ac, "mamba": mc_stacked}
            specs = {"attn": asp, "mamba": msp}
            return stack(cache, specs, G)
        if cfg.family == "encdec":
            c, s = blocks.init_decoder_cache(cfg, batch, cache_len)
            dt = layers.dtype_of(cfg)
            c = dict(c)
            c["cross_k"] = jnp.zeros((batch, cfg.enc_seq, cfg.n_kv_heads, cfg.hd), dt)
            c["cross_v"] = jnp.zeros((batch, cfg.enc_seq, cfg.n_kv_heads, cfg.hd), dt)
            s = dict(s)
            s["cross_k"] = ("batch", None, "kv_heads", None)
            s["cross_v"] = ("batch", None, "kv_heads", None)
            return stack(c, s, cfg.n_layers)
        raise ValueError(cfg.family)


def cache_len_of(cache) -> int:
    """Sequence capacity of a dense-style cache (for whisper positions)."""
    leaf = cache["k"] if isinstance(cache, dict) and "k" in cache else jax.tree.leaves(cache)[0]
    return leaf.shape[2]


@functools.lru_cache(maxsize=64)
def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
