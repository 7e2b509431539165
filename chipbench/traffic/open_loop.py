"""Open-loop request schedule for a serving cell.

A cell's file gives the mix under ``traffic``:

    {"kind": "open_loop", "rate": 4.0,
     "prompt": {"dist": "lognormal", "median": 768, "sigma": 0.8,
                "min": 64, "max": 3072},
     "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                "min": 16, "max": 512}}

A run of ``seconds`` gets ``round(rate * seconds)`` requests.  Their
inter-arrival gaps are the exponential distribution's quantiles, and their
prompt and output lengths the quantiles of the stated distributions, taken
at evenly spaced probabilities, each list shuffled once by ``ORDER_SEED``.
The run's seed draws the prompt tokens.  So every seed sends the same work
in the same order: near the knee, the order alone moved the chat cell's
p90 time to first token from 0.78 s to 1.74 s across seeds, while runs of
one seed agreed within a few percent (PERF.md).  Arrivals are offsets in
seconds from the start of the window, all inside it.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    arrival: float
    tokens: np.ndarray
    max_new_tokens: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at probabilities (i + 0.5) / n, clipped and rounded."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


ORDER_SEED = 0


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> list:
    n = max(1, round(spec["rate"] * seconds))
    order = np.random.default_rng(ORDER_SEED)
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u))
    arrivals = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals /= gaps.sum()
    prompts = order.permutation(quantiles(spec["prompt"], n))
    outputs = order.permutation(quantiles(spec["output"], n))
    rng = np.random.default_rng(seed)
    return [
        Planned(i, float(arrivals[i]),
                rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                int(outputs[i]))
        for i in range(n)
    ]
