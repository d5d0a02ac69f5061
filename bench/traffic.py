"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) is one of two kinds:

* ``backlog`` -- a closed loop that keeps ``depth_batches`` x ``max_batch``
  requests queued (MLPerf Inference's Offline scenario);
* ``poisson`` -- open-loop arrivals at ``rate`` requests per second.

Every seed gets the same work in another order: the arrival gaps are the
quantiles of the exponential distribution at ``rate``, and each length is
a quantile of its log-normal, clipped, and the seed only permutes them
and draws the token ids and images. Runs on different seeds then differ
by the order of the work, not its amount.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

from bench import seeds


def bits(precision):
    """A mix's ``"<W:I>"`` as (W, I); None for the float path."""
    if precision is None:
        return None
    w, a = precision.strip("<>").split(":")
    return int(w), int(a)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of the log-normal ``spec`` (median,
    sigma, min, max), sorted."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson stream at ``rate``: the
    exponential gaps' quantiles, permuted by the seed, summed."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = gaps[seeds.rng(seed, "arrivals").permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return due[due < seconds]


@dataclasses.dataclass
class LMRequests:
    due: np.ndarray               # (n,) seconds after the window opens
    prompts: list                 # n int32 arrays
    max_new: np.ndarray           # (n,)


def lm_requests(mix: dict, vocab: int, seconds: float, seed: int,
                rate: float | None = None) -> LMRequests:
    due = arrival_times(rate or mix["rate"], seconds, seed)
    n = len(due)
    r = seeds.rng(seed, "lengths")
    plen = lognormal_set(mix["prompt_len"], n)[r.permutation(n)]
    olen = lognormal_set(mix["output_len"], n)[r.permutation(n)]
    ids = seeds.rng(seed, "tokens")
    prompts = [ids.integers(0, vocab, int(k), dtype=np.int32) for k in plen]
    return LMRequests(due=due, prompts=prompts, max_new=olen)


def image_pool(size: int, image: int, seed: int) -> np.ndarray:
    """``size`` images (H, W, 3) of unit-normal pixels, float32."""
    return seeds.rng(seed, "images").standard_normal(
        (size, image, image, 3), dtype=np.float32)


def image_choice(n: int, pool: int, seed: int) -> np.ndarray:
    """Which pool image each of ``n`` requests carries."""
    return seeds.rng(seed, "image-choice").integers(0, pool, n)
