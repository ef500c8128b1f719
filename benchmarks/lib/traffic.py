"""The one traffic generator. A traffic mix is a data file of parameters
under ``benchmarks/traffic/``; everything here is a pure function of those
parameters and ``--seed``.

Every seed gets the same multiset of sizes and gaps in another order: the
values are the evenly spaced quantiles of the stated distribution, and the
seed only permutes them. So runs with different seeds do the same work, and
a difference between them is noise and not a different load.

Arrivals (the seeded Poisson and the exact on/off process) follow
``deeplearning4j_tpu/serving/loadgen.py`` ``ArrivalProcess``: a unit-rate
Poisson process is mapped through the inverse of the cumulative intensity,
which is exact for a piecewise-constant rate.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np


def rng_for(seed: int, what: str) -> np.random.Generator:
    """A generator for one purpose; ``seed`` may be any non-negative whole
    number (the driver's are above 2**31)."""
    return np.random.default_rng([int(seed), sum(map(ord, what))])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the distribution ``spec`` names,
    rounded to whole numbers and clipped to ``lo``..``hi``."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        x = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        x = spec["lo"] + u * (spec["hi"] - spec["lo"])
    elif kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.rint(x)
    if "lo" in spec:
        x = np.clip(x, spec["lo"], spec["hi"])
    return x.astype(np.int64)


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(quantiles(spec, n))


def _rates(arrival: dict):
    rate = float(arrival["rate_rps"])
    if rate <= 0:
        raise ValueError("rate_rps must be positive")
    if arrival["kind"] == "poisson":
        return rate, rate, 1.0, 0.0
    if arrival["kind"] != "onoff":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    return (rate * float(arrival.get("on_factor", 1.0)),
            rate * float(arrival.get("off_factor", 0.0)),
            float(arrival["on_s"]), float(arrival["off_s"]))


def _intensity(t: float, arrival: dict) -> float:
    """Expected number of arrivals in ``[0, t)``."""
    r_on, r_off, on_s, off_s = _rates(arrival)
    k, rest = divmod(t, on_s + off_s)
    return (k * (r_on * on_s + r_off * off_s) + r_on * min(rest, on_s)
            + r_off * max(rest - on_s, 0.0))


def _inverse_intensity(tau: np.ndarray, arrival: dict) -> np.ndarray:
    """Clock times at which the cumulative intensity reaches ``tau``."""
    r_on, r_off, on_s, off_s = _rates(arrival)
    k, rest = np.divmod(tau, r_on * on_s + r_off * off_s)
    t = np.where(rest <= r_on * on_s, rest / r_on,
                 on_s + (rest - r_on * on_s) / max(r_off, 1e-12))
    return k * (on_s + off_s) + t


def arrivals(arrival: dict, horizon_s: float,
             rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in ``[0, horizon_s)``. The unit-rate gaps are the
    quantiles of the exponential distribution in a seeded order, scaled so
    that they fill the horizon: every seed has the same number of arrivals
    and the same gaps, in another order."""
    total = _intensity(horizon_s, arrival)
    n = int(total)
    if n < 1:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    tau = np.cumsum(gaps) * (total / gaps.sum()) * (1.0 - 1e-9)
    return _inverse_intensity(tau, arrival)


def requests(traffic: dict, n: int, vocab_size: int, seed: int) -> List[dict]:
    """``n`` requests: prompt tokens and the number of tokens to generate.
    With ``shared_prefix`` in the mix, every prompt starts with one of
    ``groups`` seeded prefixes, whose length counts towards the prompt."""
    rng = rng_for(seed, "requests")
    p_len = lengths(traffic["prompt_len"], n, rng)
    o_len = lengths(traffic["output_len"], n, rng)
    shared = traffic.get("shared_prefix")
    prefixes = []
    if shared:
        prng = rng_for(seed, "shared_prefix")
        prefixes = [prng.integers(0, vocab_size, int(ln)).astype(np.int32)
                    for ln in lengths(shared["len"], int(shared["groups"]),
                                      prng)]
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab_size, int(p_len[i])).astype(np.int32)
        if prefixes:
            head = prefixes[i % len(prefixes)][:len(prompt) - 1]
            prompt[:len(head)] = head
        out.append({"index": i, "prompt": prompt,
                    "max_new_tokens": int(o_len[i])})
    return out


def train_batches(traffic: dict, sizes: dict, count: int, seed: int):
    """A ring of ``count`` host batches for the trainer: random tokens, the
    tokens themselves as targets, and loss weight 1 on ``loss_share`` of the
    positions (masked-LM style) or on all of them."""
    rng = rng_for(seed, "train_batches")
    B, T = int(traffic["batch"]), int(traffic["seq_len"])
    share = float(traffic.get("loss_share", 1.0))
    ring = []
    for _ in range(count):
        tokens = rng.integers(0, sizes["vocab_size"], (B, T)).astype(np.int32)
        weights = (rng.random((B, T)) < share).astype(np.float32)
        ring.append({"tokens": tokens, "targets": tokens.copy(),
                     "weights": weights})
    return ring
