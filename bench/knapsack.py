"""Knapsack instances after Pisinger, and the plain dynamic-programming
reference.

Pisinger, "Where are the hard knapsack problems?", Computers & Operations
Research 32 (2005): weights uniform in ``[1, R]``; profits uniform in
``[1, R]`` (uncorrelated) or ``w + R / 10`` (strongly correlated); the
capacity of instance ``h`` of a series of ``H`` is
``floor(h / (H + 1) * sum(w))``.
"""

from __future__ import annotations

from typing import List, NamedTuple, Tuple

import numpy as np

CLASSES = ("uncorrelated", "strongly_correlated")


class Instance(NamedTuple):
    weights: Tuple[int, ...]
    profits: Tuple[int, ...]
    capacity: int


def instance(cls: str, *, n: int, r: int, h: int, big_h: int,
             seed: int, index: int) -> Instance:
    """Instance ``index`` of the series drawn from ``seed``."""
    if cls not in CLASSES:
        raise ValueError(f"unknown instance class {cls!r}; one of {CLASSES}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    w = rng.integers(1, r + 1, n)
    if cls == "strongly_correlated":
        p = w + r // 10
    else:
        p = rng.integers(1, r + 1, n)
    capacity = h * int(w.sum()) // (big_h + 1)
    return Instance(tuple(int(x) for x in w), tuple(int(x) for x in p),
                    capacity)


def pool(cls: str, *, n: int, r: int, h: int, big_h: int, pool_seed: int,
         size: int) -> List[Instance]:
    """The traffic's fixed pool of instances."""
    return [instance(cls, n=n, r=r, h=h, big_h=big_h, seed=pool_seed,
                     index=i) for i in range(size)]


def order(seed: int, size: int) -> List[int]:
    """The run's order over the pool, drawn from its seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
    return [int(i) for i in rng.permutation(size)]


def dp_optimum(inst: Instance) -> int:
    """The plain reference: 0/1 knapsack by dynamic programming over the
    capacity, ``O(n * capacity)``."""
    best = np.zeros(inst.capacity + 1, np.int64)
    for w, p in zip(inst.weights, inst.profits):
        if w <= inst.capacity:
            best[w:] = np.maximum(best[w:], best[:-w] + p)
    return int(best[-1])
