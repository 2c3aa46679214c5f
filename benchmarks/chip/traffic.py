"""The one traffic generator: reads a mix file of ``traffic/`` and a seed.

A mix file states the loop (``open`` at a fixed rate, or ``closed`` with a
number of requests outstanding), the prompt and output length distributions
with their clips, the tier mix, and the engine's bucket ladder. Lengths,
tiers and inter-arrival gaps are each a stratified sample, the distribution's
quantiles at ``(i + 0.5) / n``, put in an order drawn from the mix's own
``schedule_seed`` (0 where it has none), not from the run's seed: every seed
sends the same requests, of the same lengths, at the same times. In a tail
such as the 95th percentile of time to first token the order is part of the
work (which requests arrive together, behind which prefill), so an order
drawn from the run's seed would change the work from seed to seed. The run's
seed draws every prompt token and every request's 64-bit noise key.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int
    tier: str  # a key of the mix's "tiers"
    key: np.ndarray  # (2,) uint32 raw PRNG key
    due: float  # seconds after the window opens (open loop); 0 for closed


def load_mix(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """Stratified sample of ``n`` lengths, clipped, as integers."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    elif kind == "fixed":
        v = np.full(n, dist["value"], np.float64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(v), dist.get("min", 1), dist.get("max", np.inf)).astype(np.int64)


def _tier_list(mix: dict, n: int) -> List[str]:
    """Exactly ``round(share * n)`` requests per tier (largest remainders)."""
    names = list(mix["tiers"])
    shares = np.array([mix["tiers"][t]["share"] for t in names], np.float64)
    shares = shares / shares.sum()
    raw = shares * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return [t for t, c in zip(names, counts) for _ in range(c)]


def n_requests(mix: dict, seconds: float) -> int:
    """Requests the run prepares: the open loop's window at its rate, or the
    closed loop's list, long enough never to run dry."""
    if mix["loop"] == "open":
        return max(1, int(math.ceil(mix["rate_per_s"] * seconds)))
    return int(mix["closed"]["requests"])


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    n = n_requests(mix, seconds)
    order = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    prompts = order.permutation(_quantiles(mix["prompt"], n))
    outputs = order.permutation(_quantiles(mix["output"], n))
    tiers = [mix_t for mix_t in np.asarray(_tier_list(mix, n))[order.permutation(n)]]
    if mix["loop"] == "open":
        # Poisson arrivals: stratified exponential gaps at the mix's rate
        u = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-u) / mix["rate_per_s"])
        dues = np.cumsum(gaps) - gaps[0]
    else:
        dues = np.zeros(n)
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint64).astype(np.uint32)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int64).astype(np.int32)
        out.append(Request(i, toks, int(outputs[i]), str(tiers[i]), keys[i], float(dues[i])))
    return out


def bucket(n: int, ladder) -> Optional[int]:
    """Smallest rung of ``ladder`` that holds ``n``, or None."""
    for b in sorted(ladder):
        if n <= b:
            return int(b)
    return None
