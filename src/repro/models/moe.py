"""Mixture-of-Experts FFN with sort-based capacity dispatch.

The GShard one-hot einsum dispatch materializes a (tokens, experts, capacity)
tensor — infeasible at 1M tokens x 128 experts. Instead we build an (E, C)
token-index table by sorting assignments by expert (MegaBlocks-style grouping
without the custom kernel), gather tokens, run batched expert einsums, and
scatter-add weighted outputs back.

Sharding: experts shard over `model` (EP) when divisible — XLA inserts the
data->expert all-to-all at the gather. Otherwise (e.g. 60 experts on a 16-way
axis) experts replicate and each expert's d_ff shards over `model` (TP-MoE).
Capacity shards over the data axes either way.

A ``dropless`` config (DeepSeek-V2) takes the other path,
:func:`held_experts_ffn`: the layer holds only its chip's share of the routed
experts (``first_held``, ``n_held``), routes over all ``n_experts``, and
computes every (token, choice) that lands on a held expert — each held
expert's rows are the step's tokens, the unchosen ones weighted 0, so no
capacity can drop a token. Choices of experts held elsewhere add nothing
here. Shared experts follow, gated (Qwen2-MoE) or not (DeepSeek).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, MoEConfig
from ..sharding import partition
from . import layers


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    D = cfg.d_model
    dt = layers.dtype_of(cfg)
    ks = jax.random.split(key, 6)
    E = m.held                      # the routed experts this layer holds
    params = {
        "router": (jax.random.normal(ks[0], (D, m.n_experts), jnp.float32) * D ** -0.5),
        "wi": layers.dense_init(ks[1], (E, D, m.d_ff_expert), D, dt),
        "wg": layers.dense_init(ks[2], (E, D, m.d_ff_expert), D, dt),
        "wo": layers.dense_init(ks[3], (E, m.d_ff_expert, D), m.d_ff_expert, dt),
    }
    specs = {
        "router": ("embed", None),
        "wi": ("experts", "embed", "mlp"),
        "wg": ("experts", "embed", "mlp"),
        "wo": ("experts", "mlp", "embed"),
    }
    if m.n_shared_experts:
        sh, sh_specs = layers.init_swiglu(ks[4], D, m.d_ff_shared, dt)
        params["shared"] = sh
        specs["shared"] = sh_specs
        if m.shared_gate:
            params["shared_gate"] = layers.dense_init(ks[5], (D, 1), D, dt)
            specs["shared_gate"] = ("embed", None)
    return params, specs


def _shared(x, p, m: MoEConfig):
    """The shared experts' output, through Qwen2-MoE's sigmoid gate where
    the config has one."""
    y = layers.swiglu(x, p["shared"])
    if m.shared_gate:
        gate = jax.nn.sigmoid(
            jnp.einsum("...d,dg->...g", x, p["shared_gate"]).astype(jnp.float32)
        ).astype(x.dtype)
        y = gate * y
    return y


def _aux_loss(probs, topi, m: MoEConfig):
    """Switch-style load-balance loss: mean router prob x top-1 share."""
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(topi[:, 0], m.n_experts, dtype=jnp.float32).mean(axis=0)
    return m.n_experts * jnp.sum(me * ce)


def held_experts_ffn(x2d: jnp.ndarray, p, m: MoEConfig):
    """Dropless routed experts of one chip's share. x2d (T, D) ->
    (y (T, D), aux, (held int32 (T,): each token's choices of a held
    expert, rows int32: expert rows computed))."""
    T, D = x2d.shape
    E = m.held
    with jax.named_scope("moe_dispatch"):
        topw, topi, probs = route(x2d, p["router"], m)
        local = topi - m.first_held                               # (T, k)
        held = (local >= 0) & (local < E)
        # weight of each held expert for each token, 0 where not chosen
        w = jnp.einsum("tk,tke->te", jnp.where(held, topw, 0.0),
                       jax.nn.one_hot(local, E, dtype=jnp.float32))
    with jax.named_scope("moe_experts"):
        # every held expert over the step's T tokens: rows bounded by the
        # step, never by a capacity
        h = jnp.einsum("td,edf->etf", x2d, p["wi"])
        g = jnp.einsum("td,edf->etf", x2d, p["wg"])
        h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
        ye = jnp.einsum("etf,efd->etd", h, p["wo"])
    with jax.named_scope("moe_combine"):
        y = jnp.einsum("etd,te->td", ye, w.astype(ye.dtype))
    counts = (held.sum(axis=1, dtype=jnp.int32), jnp.int32(E * T))
    return y, _aux_loss(probs, topi, m), counts


def _capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(m.capacity_factor * n_tokens * m.top_k / m.n_experts)
    c = max(c, 4)
    return int(-(-c // 4) * 4)  # round up to a multiple of 4


def route(x2d: jnp.ndarray, router_w: jnp.ndarray, m: MoEConfig):
    """Returns (top-k weights (T,k) fp32, top-k expert ids (T,k) int32,
    router probs for aux loss (T,E))."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi, probs


def build_dispatch(topi: jnp.ndarray, topw: jnp.ndarray, n_tokens: int, m: MoEConfig):
    """Sort assignments by expert; keep the first C per expert (capacity
    drop). Returns (gather_idx (E*C,) int32 in [0, T] where T = dropped,
    combine_w (E*C,) fp32, C, assign_slot (T, k) int32 in [0, E*C] — the slot
    each (token, choice) landed in, E*C when dropped)."""
    E, k = m.n_experts, m.top_k
    C = _capacity(n_tokens, m)
    flat_e = topi.reshape(-1)                               # (T*k,)
    order = jnp.argsort(flat_e)                             # stable, groups by expert
    sorted_e = flat_e[order]
    # rank of each assignment within its expert group
    group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    ranks = jnp.arange(sorted_e.shape[0], dtype=jnp.int32) - group_start.astype(jnp.int32)
    keep = ranks < C
    slot = jnp.where(keep, sorted_e * C + ranks, E * C)     # overflow -> dropped slot
    token_of = (order // k).astype(jnp.int32)
    w_of = topw.reshape(-1)[order]
    gather_idx = jnp.full((E * C + 1,), n_tokens, jnp.int32).at[slot].set(token_of)[: E * C]
    combine_w = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(w_of)[: E * C]
    # invert the permutation: slot of each original (token, choice) assignment
    assign_slot = (
        jnp.zeros((n_tokens * k,), jnp.int32).at[order].set(slot.astype(jnp.int32))
    ).reshape(n_tokens, k)
    return gather_idx, combine_w, C, assign_slot


def _local_expert_ffn(x2d, p, m: MoEConfig, e_base: int, n_local: int):
    """Dispatch+compute+combine for `n_local` experts starting at `e_base`,
    entirely on-device (no collectives). x2d: (T_loc, D) local tokens."""
    T, D = x2d.shape
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    C = _capacity(T, m)
    local = topi - e_base                                   # (T, k); valid in [0, n_local)
    valid = (local >= 0) & (local < n_local)
    flat_e = jnp.where(valid, local, n_local).reshape(-1)   # invalid -> overflow group
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    ranks = jnp.arange(sorted_e.shape[0], dtype=jnp.int32) - group_start.astype(jnp.int32)
    keep = (ranks < C) & (sorted_e < n_local)
    slot = jnp.where(keep, sorted_e * C + ranks, n_local * C)
    token_of = (order // m.top_k).astype(jnp.int32)
    w_of = topw.reshape(-1)[order]
    gather_idx = jnp.full((n_local * C + 1,), T, jnp.int32).at[slot].set(token_of)[: n_local * C]
    combine_w = jnp.zeros((n_local * C + 1,), jnp.float32).at[slot].set(w_of)[: n_local * C]

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    xe = x_pad[gather_idx].reshape(n_local, C, D)
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    y_flat = ye.reshape(n_local * C, D) * combine_w[:, None].astype(ye.dtype)
    y = jnp.zeros((T + 1, D), ye.dtype).at[gather_idx].add(y_flat)[:T]

    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(topi[:, 0], m.n_experts, dtype=jnp.float32).mean(axis=0)
    aux = m.n_experts * jnp.sum(me * ce)
    return y, aux


def _moe_ffn_shard_map(x: jnp.ndarray, p, cfg: ModelConfig):
    """EP via shard_map: activations are replicated over `model` while
    experts shard over it, so NO dispatch collective is needed at all —
    each model-rank routes its (data-)local tokens to its local experts and
    the partial outputs reduce with one psum of (T_loc, D). This is the
    §Perf fix for the dense all-reduces XLA's SPMD partitioner emits for the
    global scatter/gather formulations (see EXPERIMENTS.md)."""
    from jax.sharding import PartitionSpec as P

    ctx = partition.current()
    mesh = ctx.mesh
    m = cfg.moe
    n_model = mesh.shape.get("model", 1)
    n_local = m.n_experts // n_model
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    B, S, D = x.shape

    def body(xb, router, wi, wg, wo):
        rank = jax.lax.axis_index("model")
        x2d = xb.reshape(-1, D)
        pp = {"router": router, "wi": wi, "wg": wg, "wo": wo}
        y, aux = _local_expert_ffn(x2d, pp, m, rank * n_local, n_local)
        y = jax.lax.psum(y, "model")
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y.reshape(xb.shape), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes if batch_axes else None),      # x: batch sharded
            P(),                                         # router replicated
            P("model"), P("model"), P("model"),          # experts over model
        ),
        out_specs=(P(batch_axes if batch_axes else None), P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return y, aux


def moe_ffn(x: jnp.ndarray, p, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B,S,D), aux load-balance loss scalar)."""
    y, aux, _ = moe_layer(x, p, cfg)
    return y, aux


def moe_layer(x: jnp.ndarray, p, cfg: ModelConfig):
    """x: (B, S, D) -> (out (B,S,D), aux loss, routing counts (held int32
    (B*S,): each token's choices of an expert held here, rows int32: expert
    rows computed, padding included))."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    x2d = x.reshape(T, D)
    if m.dropless:
        y, aux, counts = held_experts_ffn(x2d, p, m)
        y = y.reshape(B, S, D)
        if m.n_shared_experts:
            y = y + _shared(x, p, m)
        return y, aux, counts
    assert m.held == m.n_experts, "a share of the experts needs the dropless path"

    ctx = partition.current()
    if (
        cfg.moe_impl == "local"
        and ctx is not None
        and ctx.mesh is not None
        and ctx.mesh.shape.get("model", 1) > 1
        and m.n_experts % ctx.mesh.shape.get("model", 1) == 0
    ):
        y, aux = _moe_ffn_shard_map(x, p, cfg)
        if m.n_shared_experts:
            y = y + _shared(x, p, m)
        n_model = ctx.mesh.shape.get("model", 1)
        return y, aux, _capacity_counts(T, m, _capacity(T // n_model, m) * n_model)

    topw, topi, probs = route(x2d, p["router"], m)
    gather_idx, combine_w, C, assign_slot = build_dispatch(topi, topw, T, m)

    # dispatch: (E, C, D); padded row T reads zeros
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    xe = x_pad[gather_idx].reshape(m.n_experts, C, D)
    xe = partition.shard_act(xe, "experts", "capacity", "embed")

    # expert FFN (SwiGLU), batched over experts
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    h = partition.shard_act(h, "experts", "capacity", "mlp")
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])

    if getattr(cfg, "moe_combine", "scatter") == "gather":
        # combine as a token-side GATHER: each token pulls its k expert
        # outputs by slot id. XLA partitions gathers with all-to-all-sized
        # traffic; the scatter form below degenerates into dense all-reduces
        # of the full (T, D) activation (the §Perf hillclimb finding).
        ye_pad = jnp.concatenate(
            [ye.reshape(m.n_experts * C, D), jnp.zeros((1, D), ye.dtype)], axis=0
        )
        picked = ye_pad[assign_slot.reshape(-1)].reshape(T, m.top_k, D)
        y = jnp.einsum("tkd,tk->td", picked, topw.astype(picked.dtype))
    else:
        # combine: weighted scatter-add back to token order
        y_flat = ye.reshape(m.n_experts * C, D) * combine_w[:, None].astype(ye.dtype)
        y = jnp.zeros((T + 1, D), ye.dtype).at[gather_idx].add(y_flat)[:T]
    y = y.reshape(B, S, D)
    y = partition.shard_act(y, "batch", "seq", None)

    if m.n_shared_experts:
        y = y + _shared(x, p, m)
    return y, _aux_loss(probs, topi, m), _capacity_counts(T, m, C)


def _capacity_counts(T: int, m: MoEConfig, C: int):
    """Routing counts of the capacity path: every expert is held here."""
    return jnp.full((T,), m.top_k, jnp.int32), jnp.int32(m.n_experts * C)
