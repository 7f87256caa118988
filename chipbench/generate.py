"""The one traffic generator: reads a mix's parameters, draws from the seed.

Every seed gets the same multiset of sizes and gaps (a stratified draw at
fixed quantiles of each law); the seed shuffles their order and draws the
token ids, so two seeds do the same amount of work. A mix that fixes
``order_seed`` keeps one order for every seed: under an open loop the order
decides the queueing, and with it the tails.

Laws (``traffic/<name>.json``):

* lengths ``{"law": "lognormal", "median": m, "sigma": s}``, then either
  ``"buckets": [...]`` (rounded up to the next bucket) or ``"min"``/``"max"``
  (rounded to a token and clipped);
* arrivals ``{"kind": "poisson", "rate": r}`` (open loop, requests per
  second) or ``{"kind": "backlog", "count": n}`` (all queued at t = 0).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

SALT = {"order": 1, "tokens": 2, "gaps": 3, "prompt": 4, "output": 5}


def rng(seed: int, stream: str, *extra: int) -> np.random.Generator:
    """Independent generator per (seed, stream[, extra]); any seed >= 0."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  SALT[stream], *extra])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: dict, n: int, seed: int, stream: str) -> np.ndarray:
    z = np.asarray([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(law["median"]) + law["sigma"] * z)
    if "buckets" in law:
        b = np.asarray(sorted(law["buckets"]))
        x = b[np.minimum(np.searchsorted(b, x, side="left"), len(b) - 1)]
    else:
        x = np.clip(np.rint(x), law["min"], law["max"])
    return rng(seed, stream).permutation(x.astype(np.int64))


def arrivals(spec: dict, n: int, seed: int) -> np.ndarray:
    """Due times in seconds from the start of traffic, ascending."""
    if spec["kind"] == "backlog":
        return np.zeros(n)
    if spec["kind"] == "poisson":
        gaps = -np.log1p(-_quantiles(n)) / spec["rate"]
        return np.cumsum(rng(seed, "gaps").permutation(gaps))
    raise ValueError(f"unknown arrival kind {spec['kind']!r}")


def request_count(traffic: dict, seconds: float) -> int:
    """Requests to draw so that the stream outlasts warm-up plus window."""
    a = traffic["arrivals"]
    if a["kind"] == "backlog":
        return int(a["count"])
    horizon = traffic["warmup_s"] + seconds
    return int(math.ceil(a["rate"] * horizon * 1.25)) + 64


def requests(traffic: dict, seed: int, seconds: float):
    """(due_s, prompt_len, max_new_tokens) arrays. A mix with an
    ``order_seed`` gives every run the same sequence of gaps and lengths
    (the run's seed then draws only the token ids)."""
    n = request_count(traffic, seconds)
    order = traffic.get("order_seed", seed)
    due = arrivals(traffic["arrivals"], n, order)
    plen = lengths(traffic["prompt"], n, order, "prompt")
    out = lengths(traffic["output"], n, order, "output")
    return due, plen, out


def prompt_tokens(seed: int, i: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``i``: drawn on demand, the same for a seed."""
    return rng(seed, "tokens", i).integers(0, vocab, length, dtype=np.int32)


def token_batch(seed: int, step: int, shape, vocab: int):
    """(tokens, labels) of training step ``step``: next-token targets over
    rows that all differ."""
    t = rng(seed, "tokens", step).integers(0, vocab, tuple(shape[:-1])
                                           + (shape[-1] + 1,), dtype=np.int32)
    return t[..., :-1], t[..., 1:]
