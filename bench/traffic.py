"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes that mix's requests from a seed.

Every seed gets the same schedule.  Prompt lengths, output lengths and
inter-arrival gaps are stratified quantiles of the mix's distributions
(one per request, at probabilities (i + 0.5) / n), put in an order drawn
from the mix's own ``schedule_seed``; ``--seed`` draws only the token
ids (and the weights).  So runs with different seeds do the same work at
the same times, and their spread is the system's, not the draw's: with
the order left to the run's seed, which requests meet in the queue moved
the median TTFT of pt-6b-d4.chat by 28 % between seeds.

One kind of mix, ``open_loop``: ``rate_rps`` requests per second, due
at fixed times, floor(rate x seconds) requests, all due inside the
window.

Lengths: {"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}, rounded to whole tokens and clipped to [a, b].
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Item:
    due: float                    # seconds after the window opens
    prompt: List[int]
    max_new: int


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise SystemExit(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process: quantiles of Exp(rate)."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def _prompt(rng: np.random.Generator, length: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=length).tolist()


def open_loop(mix: Dict[str, Any], seconds: float, seed: int,
              vocab: int) -> List[Item]:
    n = int(math.floor(mix["rate_rps"] * seconds))
    rng = _rng(mix["schedule_seed"], 0)
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outputs = rng.permutation(quantiles(mix["output"], n))
    due = np.cumsum(rng.permutation(exp_gaps(mix["rate_rps"], n)))
    tok = _rng(seed, 1)
    return [Item(float(d), _prompt(tok, int(p), vocab), int(o))
            for d, p, o in zip(due, prompts, outputs)]


def longest(mix: Dict[str, Any]) -> int:
    """Most cache positions one request of the mix can reserve."""
    return mix["prompt"]["max"] + mix["output"]["max"] - 1
