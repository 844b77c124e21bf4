"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``
and turns it into requests, from ``--seed``.

Every seed gets the same multiset of sizes, budgets and inter-arrival
gaps, in another order: each is drawn at stratified quantiles
``(i + 0.5) / n`` of its distribution and then shuffled by the seed's
generator (``numpy.random.default_rng``, as ``serve/traffic.py`` seeds
its traces).  So two seeds give the same work and differ only in
ordering and in token ids, and a run's spread measures the system, not
the draw.

Mix kinds (``"kind"`` in the file):

* ``open_loop``: Poisson arrivals at ``rate_per_s`` for the window,
  each with a prompt length, an output length and a budget;
* ``backlog``: an endless stream of requests from the same length
  tables, topped up by the cell so that as many requests as it has
  slots always wait;
* ``image_batches``: batches of ``batch`` images from a pool made on the
  device, each image with a budget naming one menu configuration.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths at stratified lognormal quantiles, shuffled."""
    z = np.asarray([_NORMAL.inv_cdf(q) for q in quantiles(n)])
    v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    v = np.clip(v, spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(v)


def gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """n exponential inter-arrival gaps at stratified quantiles, shuffled."""
    return rng.permutation(-np.log1p(-quantiles(n)) / rate)


def balanced(values: list, n: int, rng: np.random.Generator) -> list:
    """n picks cycling through ``values`` (equal shares), shuffled."""
    return [values[i] for i in rng.permutation(np.arange(n) % len(values))]


@dataclasses.dataclass
class Request:
    t_sched: float               # seconds after the window opens
    prompt: np.ndarray           # int32 token ids
    max_new: int
    budget: float


def open_loop(mix: dict, seconds: float, vocab: int, seed: int
              ) -> List[Request]:
    """The window's arrivals: ``round(rate * seconds)`` requests whose
    gaps add up to just under the window."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    g = gaps(mix["rate_per_s"], n, rng_for(seed, 10))
    t = np.cumsum(g) * (seconds * n / (n + 1) / g.sum())
    return _requests(mix, n, vocab, seed, t)


def backlog(mix: dict, vocab: int, seed: int, n: int = 4096
            ) -> List[Request]:
    """A table of ``n`` requests (all due at once) to draw from in order."""
    return _requests(mix, n, vocab, seed, np.zeros((n,)))


def _requests(mix: dict, n: int, vocab: int, seed: int, t) -> List[Request]:
    p = lengths(mix["prompt"], n, rng_for(seed, 11))
    o = lengths(mix["output"], n, rng_for(seed, 12))
    b = balanced(list(mix["budgets"]), n, rng_for(seed, 13))
    tok = rng_for(seed, 14)
    return [Request(float(t[i]), tok.integers(0, vocab, int(p[i]),
                                              dtype=np.int32),
                    int(o[i]), float(b[i])) for i in range(n)]


def image_budgets(names: list, batch: int, n_batches: int, seed: int
                  ) -> List[list]:
    """Per batch, one menu-configuration name per image (equal shares)."""
    rng = rng_for(seed, 20)
    return [balanced(list(names), batch, rng) for _ in range(n_batches)]


def pick(n_items: int, k: int, seed: int, must: Optional[int] = None
         ) -> List[int]:
    """A seed-drawn sample of k indices of n_items, with ``must`` in it."""
    rng = rng_for(seed, 30)
    idx = [int(i) for i in rng.permutation(n_items)[:k]]
    if must is not None and must not in idx:
        idx = [must] + idx[:k - 1]
    return sorted(idx)
