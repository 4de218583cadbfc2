"""The one generator of traffic: reads a mix's parameter file, draws from --seed.

Every seed gets the same set of sizes and gaps between arrivals, in another
order: each quantity is drawn by stratified quantiles of its distribution
(n values at the midpoints (i + 0.5) / n) and the seed permutes them. Runs of
different seeds then differ in which request comes when and in the tokens and
payload contents, not in how much work there is.

A mix that names a ``schedule_seed`` orders its sizes and gaps by that seed
instead: every run then offers one schedule, as a load generator with a
fixed schedule seed does, and ``--seed`` draws the tokens, the weights and
the sample that is checked. A window of a few long requests needs it, since
there the order alone decides how many are in flight at once.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator per (seed, stream); any non-negative seed, wide ones too."""
    return np.random.default_rng([int(seed), *stream])


def quantiles(spec: Dict, n: int) -> np.ndarray:
    """n values of `spec`'s distribution at the midpoints of n strata."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform_int":
        lo, hi = spec["min"], spec["max"]
        vals = lo + np.floor(u * (hi - lo + 1))
    elif kind == "choice":
        w = np.asarray(spec["weights"], float)
        edges = np.cumsum(w / w.sum())
        vals = np.asarray(spec["values"])[np.searchsorted(edges, u)]
    elif kind == "fixed":
        vals = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec:
        vals = np.maximum(vals, spec["min"])
    if "max" in spec:
        vals = np.minimum(vals, spec["max"])
    vals = np.ceil(vals).astype(np.int64)
    if "round_up_to" in spec:
        buckets = np.asarray(sorted(spec["round_up_to"]))
        vals = buckets[np.searchsorted(buckets, vals)]
    return vals


def arrival_times(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at `rate` per second:
    exponential gaps by stratified quantiles, in the seed's order."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return times * (seconds * (n - 0.5) / n) / max(times[-1] + gaps[-1], 1e-9)


@dataclass
class Request:
    idx: int
    due: float                  # seconds after the window opens
    prompt_len: int = 0
    out_len: int = 0


def schedule_seed(mix: Dict, seed: int) -> int:
    """The seed that orders the mix's sizes and arrivals."""
    return int(mix.get("schedule_seed", seed))


def sessions(mix: Dict, seed: int, seconds: float) -> List[Request]:
    """An open loop of generation requests (serving mixes)."""
    order = schedule_seed(mix, seed)
    times = arrival_times(mix["rate_per_s"], seconds, rng_for(order, 1))
    n = len(times)
    prompts = rng_for(order, 2).permutation(quantiles(mix["prompt_tokens"], n))
    outs = rng_for(order, 3).permutation(quantiles(mix["output_tokens"], n))
    return [Request(i, float(t), int(p), int(o))
            for i, (t, p, o) in enumerate(zip(times, prompts, outs))]


def tasks(mix: Dict, seed: int, seconds: float) -> Optional[List[Request]]:
    """An open loop of function tasks; None for a closed loop, whose tasks
    are made as the loop asks for them."""
    if mix["loop"] == "closed":
        return None
    times = arrival_times(mix["rate_per_s"], seconds, rng_for(schedule_seed(mix, seed), 1))
    return [Request(i, float(t)) for i, t in enumerate(times)]


def token_ids(seed: int, idx: int, n: int, vocab: int) -> np.ndarray:
    return rng_for(seed, 4, idx).integers(0, vocab, n, dtype=np.int64).astype(np.int32)


def sample_indices(seed: int, population: List[int], k: int, must: Optional[int] = None
                   ) -> List[int]:
    """k members of `population` drawn from the seed, `must` among them."""
    pop = sorted(population)
    rng = rng_for(seed, 5)
    picked = [] if must is None else [must]
    rest = [p for p in pop if p != must]
    picked += [int(x) for x in rng.permutation(rest)[: max(0, k - len(picked))]]
    return sorted(picked)
