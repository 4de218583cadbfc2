"""Roofline-term extraction from compiled dry-run artifacts.

Three terms, all in per-chip seconds (cost_analysis of an SPMD-partitioned
module reports PER-DEVICE flops/bytes — verified empirically):

    compute    = flops_per_device / peak_flops
    memory     = hbm_bytes_per_device / hbm_bw
    collective = wire_bytes_per_device / ici_bw

collective bytes are NOT in cost_analysis: we parse the optimized HLO
(compiled.as_text()) and sum per-op wire traffic with ring-algorithm factors:
    all-reduce      2·S·(n-1)/n      (reduce-scatter + all-gather phases)
    all-gather      R·(n-1)/n        (R = result bytes)
    reduce-scatter  R·(n-1)          (input = n·R; each device moves (n-1)·R)
    all-to-all      R·(n-1)/n
    collective-permute  R
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

# TPU v5e-class constants (per chip)
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s effective per chip (≈1 link busy)
    "hbm_bytes": 16 * 2**30,     # capacity
}

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_OP_RE = re.compile(
    r"=\s*(?P<result>.*?)\s+(?P<op>all-reduce-start|all-gather-start|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute|"
    r"all-reduce|all-gather)\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _shape_bytes(result: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        ids = [t for t in m.group(1).split(",") if t.strip()]
        return max(len(ids), 1)
    return 1


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    result_bytes: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    def to_dict(self) -> dict:
        return {
            "counts": dict(self.counts),
            "result_bytes": dict(self.result_bytes),
            "wire_bytes": {k: int(v) for k, v in self.wire_bytes.items()},
            "total_wire_bytes": int(self.total_wire_bytes),
        }


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if not any(c in line for c in _COLLECTIVES):
            continue
        m = _OP_RE.search(line)
        if m is None:
            continue
        op = m.group("op").replace("-start", "")
        rbytes = _shape_bytes(m.group("result"))
        n = _group_size(line)
        if n <= 1:
            continue  # single-participant: no wire traffic
        if op == "all-reduce":
            wire = 2 * rbytes * (n - 1) / n
        elif op == "all-gather":
            wire = rbytes * (n - 1) / n
        elif op == "reduce-scatter":
            wire = rbytes * (n - 1)
        elif op == "all-to-all":
            wire = rbytes * (n - 1) / n
        else:  # collective-permute
            wire = rbytes
        stats.counts[op] = stats.counts.get(op, 0) + 1
        stats.result_bytes[op] = stats.result_bytes.get(op, 0) + rbytes
        stats.wire_bytes[op] = stats.wire_bytes.get(op, 0) + wire
    return stats


def modeled_hbm_bytes(cfg, shape, n_chips: int, model_axis: int = 16) -> dict:
    """Analytic per-device HBM traffic for the TPU-fused execution (flash
    attention keeps S^2 scores in VMEM; fusions keep elementwise chains out
    of HBM). The XLA-CPU 'bytes accessed' is reported alongside as the
    unfused upper bound — on CPU every materialized S^2 score tensor counts,
    which the TPU target never writes.

    Terms (documented coarse constants):
      params  train: 8x bf16 param bytes (fwd read, bwd read, remat read,
              grad write) + 24x fp32-equivalent optimizer r/w + 2x write-back
              prefill/decode: one bf16 read
      acts    per layer: residual/proj I/O ~8 D-wide + 4 F-wide passes per
              token, x3 for train (fwd+remat+bwd), x1 inference
      attn    flash traffic: q,k,v,o only (+cache r/w at decode)
    """
    N_loc = cfg.param_count() / n_chips
    data_total = max(n_chips // model_axis, 1)
    bpe = 2  # bf16

    if shape.kind == "train":
        param_traffic = (4 * 2 + 24 + 2) * N_loc  # ~34 bytes/param/step
        tokens_loc = shape.global_batch * shape.seq_len / data_total
        passes = 3
    elif shape.kind == "prefill":
        param_traffic = 2 * N_loc
        tokens_loc = shape.global_batch * shape.seq_len / data_total
        passes = 1
    else:  # decode
        param_traffic = 2 * N_loc
        tokens_loc = shape.global_batch / data_total
        passes = 1

    D = cfg.d_model
    if cfg.family == "moe":
        F_eff = cfg.moe.top_k * cfg.moe.d_ff_expert + (
            cfg.moe.d_ff_shared if cfg.moe.n_shared_experts else 0
        )
    elif cfg.family in ("ssm", "hybrid"):
        F_eff = 2 * cfg.ssm.d_inner(D)
    else:
        F_eff = cfg.d_ff
    act_per_layer = tokens_loc * (8 * D + 4 * F_eff / max(model_axis, 1)) * bpe
    act_traffic = cfg.n_layers * act_per_layer * passes

    cache_traffic = 0.0
    if shape.kind == "decode":
        from ..serving.kv_cache import cache_bytes

        cache_traffic = 2.0 * cache_bytes(cfg, shape.global_batch, shape.seq_len) / n_chips

    total = param_traffic + act_traffic + cache_traffic
    return {
        "total": float(total),
        "param_traffic": float(param_traffic),
        "act_traffic": float(act_traffic),
        "cache_traffic": float(cache_traffic),
    }


def roofline_terms(
    flops_per_device: float,
    hbm_bytes_per_device: float,
    wire_bytes_per_device: float,
    model_flops_total: Optional[float] = None,
    n_chips: int = 256,
) -> dict:
    t_compute = flops_per_device / HW["peak_flops_bf16"]
    t_memory = hbm_bytes_per_device / HW["hbm_bw"]
    t_collective = wire_bytes_per_device / HW["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_collective}
    bottleneck = max(terms, key=terms.get)
    out = {
        **terms,
        "bottleneck": bottleneck.replace("_s", ""),
        "step_time_lower_bound_s": max(terms.values()),
    }
    if model_flops_total is not None:
        hlo_total = flops_per_device * n_chips
        out["model_flops_total"] = model_flops_total
        out["useful_flops_ratio"] = model_flops_total / hlo_total if hlo_total else 0.0
        # roofline fraction: useful model FLOPs per second at the bound step
        # time, relative to the fleet's peak
        t = out["step_time_lower_bound_s"]
        out["roofline_fraction"] = (
            model_flops_total / t / (n_chips * HW["peak_flops_bf16"]) if t > 0 else 0.0
        )
    return out


def extract_costs(compiled) -> dict:
    """Static per-device costs of one compiled module (flops / HBM bytes /
    collective wire bytes)."""
    cost = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return {
        "flops_per_device": float(cost.get("flops", 0.0)),
        "hbm_bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "wire_bytes_per_device": float(colls.total_wire_bytes),
        "collectives": colls.to_dict(),
    }


def extrapolate(base: dict, two_units: dict, units: int) -> dict:
    """Depth calibration: cost(L) = cost(L1) + (units-1) * (cost(L2)-cost(L1)).
    Exact for layer-homogeneous stacks; recovers what XLA's cost analysis
    hides inside lax.scan bodies (counted once regardless of trip count)."""
    out = {}
    for k in ("flops_per_device", "hbm_bytes_per_device", "wire_bytes_per_device"):
        delta = two_units[k] - base[k]
        out[k] = base[k] + (units - 1) * delta
        out[k + "_per_layer"] = delta
    out["collectives_base"] = base["collectives"]
    out["collectives_delta"] = two_units["collectives"]
    out["units"] = units
    return out


def analyze_compiled(compiled, n_chips: int, model_flops_total: Optional[float] = None) -> dict:
    mem = compiled.memory_analysis()
    peak = mem.peak_memory_in_bytes
    cost = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(
        flops, hbm_bytes, colls.total_wire_bytes,
        model_flops_total=model_flops_total, n_chips=n_chips,
    )
    return {
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": peak,
            # XLA's peak counts every live buffer: arguments (weights,
            # caches; donated ones alias into outputs), outputs and temps
            # (tests/test_tpu_compile.py checks it on the full decode step)
            "resident_bytes": peak,
            "resident_gib": round(peak / 2**30, 3),
            "fits_hbm": bool(peak <= HW["hbm_bytes"]),
        },
        "cost": {
            "flops_per_device": flops,
            "hbm_bytes_per_device": hbm_bytes,
        },
        "collectives": colls.to_dict(),
        "roofline": terms,
    }
