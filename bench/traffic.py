"""The one traffic generator. It reads a traffic file's parameters and the
run's seed and returns the requests or batches of a run.

Steadiness: lengths and inter-arrival gaps are the stratified quantiles of
their distributions, so every seed gets the same set of sizes and gaps, in
an order drawn from the seed; token ids are drawn from the seed.

Distributions (a dict with ``dist``):
  lognormal  ``median``, ``sigma``, clipped to [``min``, ``max``]
  uniform    integers in [``min``, ``max``]
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def quantile_values(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
        v = np.clip(np.rint(v), dist["min"], dist["max"])
    elif kind == "uniform":
        v = np.floor(dist["min"] + u * (dist["max"] - dist["min"] + 1))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return v.astype(np.int64)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


@dataclasses.dataclass
class Req:
    index: int
    due: float            # seconds after the generator's start (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def requests(traffic: dict, n: int, seed: int, vocab: int) -> list[Req]:
    """``n`` requests: prompt and output lengths from their distributions,
    each permuted by the seed; open loop adds Poisson arrivals (gaps at the
    quantiles of an exponential at ``rate_per_s``, permuted)."""
    plen = rng(seed, 1).permutation(quantile_values(traffic["prompt"], n))
    olen = rng(seed, 2).permutation(quantile_values(traffic["output"], n))
    due = np.zeros(n)
    if traffic["loop"] == "open":
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u) / traffic["rate_per_s"]
        due = np.cumsum(rng(seed, 3).permutation(gaps)) - gaps.mean()
    toks = rng(seed, 4)
    return [Req(i, float(due[i]),
                toks.integers(0, vocab, int(plen[i]), dtype=np.int32),
                int(olen[i])) for i in range(n)]


def open_loop_count(traffic: dict, seconds: float) -> int:
    """Requests to cover the fill before the window, the window and the
    drain after it at the traffic's rate."""
    span = traffic["pre_window_s"] + seconds + traffic["drain_s"]
    return int(math.ceil(traffic["rate_per_s"] * span))


def closed_loop_count(traffic: dict, seconds: float) -> int:
    """Enough requests that no client runs dry: every client's requests
    can at most be the shortest output long, so cover the run at the
    traffic's ``max_tokens_per_s`` of all clients together."""
    span = traffic["pre_window_s"] + seconds + traffic["drain_s"]
    per_client = span * traffic["max_tokens_per_s"] / traffic["clients"]
    return traffic["clients"] * (1 + int(per_client // traffic["output"]["min"]))


def train_batch(key, step, batch: int, seq_len: int, vocab: int):
    """Tokens and next-token labels of one training step, on the device:
    ``batch`` rows of ``seq_len + 1`` ids, uniform over the vocabulary."""
    import jax

    ids = jax.random.randint(jax.random.fold_in(key, step),
                             (batch, seq_len + 1), 0, vocab, dtype="int32")
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
