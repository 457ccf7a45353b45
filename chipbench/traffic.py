"""The one generator of every traffic mix, from a data file of parameters.

A mix file (``chipbench/traffic/<name>.json``) names its ``kind``:

* ``open_loop``: independent users; requests are due on a schedule
  whether or not earlier ones have finished.  ``rate_per_s`` sets the
  mean gap; ``prompt`` and ``output`` give the length distributions.
* ``closed_loop``: ``clients`` callers, each sending its next request
  when its previous one finished.
* ``packed_train``: training batches of ``batch`` rows of ``seq_len``
  tokens, drawn by the program's own input pipeline.

Every seed gets the same multiset of lengths and of arrival gaps
(stratified quantiles of the distribution) in another order, and its
own token ids: seeds then differ in what they send and not in how much
work the window holds.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float          # open loop: seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any non-negative int)."""
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def seed32(seed: int) -> int:
    """A 32-bit key for JAX and for the program, derived from ``seed``."""
    return int(np.random.SeedSequence([seed]).generate_state(1)[0])


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """n lengths at the stratified quantiles (i + 1/2) / n of ``dist``."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _prompts(rng, lengths, vocab: int) -> List[np.ndarray]:
    # ids 0 and 1 are the pad and end-of-document ids of the program
    return [rng.integers(2, vocab, int(n), dtype=np.int32) for n in lengths]


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int
              ) -> List[Request]:
    """Requests due in [0, seconds), in order of their due time."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 1)
    # exponential quantiles
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    plen = rng.permutation(quantile_lengths(mix["prompt"], n))
    olen = rng.permutation(quantile_lengths(mix["output"], n))
    prompts = _prompts(rng, plen, vocab)
    return [Request(float(d), p, int(o)) for d, p, o
            in zip(due, prompts, olen) if d < seconds]


def closed_loop(mix: Dict, seed: int, vocab: int) -> List[List[Request]]:
    """Each client's queue of requests, ``per_client`` deep."""
    C, R = int(mix["clients"]), int(mix["per_client"])
    rng = rng_for(seed, 2)
    plen = rng.permutation(quantile_lengths(mix["prompt"], C * R))
    olen = rng.permutation(quantile_lengths(mix["output"], C * R))
    prompts = _prompts(rng, plen, vocab)
    reqs = [Request(0.0, p, int(o)) for p, o in zip(prompts, olen)]
    return [reqs[c::C] for c in range(C)]


def warmup_lengths(mix: Dict, chunk: int, prefill_len: int) -> List[int]:
    """One prompt length per prefill bucket this mix can use."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    first = -(-lo // chunk) * chunk
    last = min(prefill_len, -(-hi // chunk) * chunk)
    return list(range(first, last + 1, chunk))
