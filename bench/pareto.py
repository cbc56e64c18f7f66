"""The Fig. 9 DAG with Bounded Pareto per-node cost: the plain reference.

The DAG is ``bench/dag.py``'s complete ``fanout``-ary tree over the ids
``0 .. n_nodes - 1``.  Node ``v`` costs ``c(v)`` units, one unit one
SHA-1 compression, as UTS counts a node's work (Olivier et al., LCPC
2006):

* unit 0 is ``h_0 = SHA1(16 zero bytes || v)``, ``v`` as 4 bytes
  big-endian (UTS's ``rng_init``);
* unit ``i`` is ``h_i = SHA1(h_{i-1} || i)``, ``i`` as 4 bytes
  big-endian (UTS's ``rng_spawn``), for ``i = 1 .. c(v) - 1``;
* ``c(v) = floor(X)``, ``X`` Bounded Pareto on ``[k, p)`` with shape
  ``alpha`` (Harchol-Balter, Crovella and Murta, JPDC 59(2), 1999),
  drawn from ``h_0``'s UTS draw (bytes 16..19, big-endian, ``&
  0x7fffffff``): ``c = k + #{j in k .. p-2 : draw >= T[j]}``, ``T[j] =
  ceil(2**31 * F(j + 1))``, ``F(x) = (1 - (k/x)**alpha) / (1 -
  (k/p)**alpha)`` in float64.

The final digest ``h_{c(v)-1}`` of each node enters ``bench/uts.py``'s
salted checksum.  :func:`reference` walks the tree with :mod:`hashlib`
and gives the node count, the sum of the ids, the sum of the costs and
the checksum.  The chains depend on the ids alone, so they are walked
once a process; the seed's salt only folds their final digests.  This
module imports nothing of the program.
"""

from __future__ import annotations

import bisect
import hashlib
import math
from typing import Dict, List, Tuple

import numpy as np

from bench import uts

_MASK32 = 0xFFFFFFFF
_CHAINS: Dict[Tuple, Tuple[int, int, int, np.ndarray]] = {}


def salt_for(seed: int) -> int:
    """The 32-bit salt of the checksum, drawn from the run's seed."""
    return int(np.random.default_rng(np.random.SeedSequence([seed, 19]))
               .integers(0, 2**32, dtype=np.uint64))


def thresholds(alpha: float, k: int, p: int) -> List[int]:
    """``T[k] .. T[p-2]`` of the law, each ``ceil(2**31 * F(j + 1))``."""
    denom = 1.0 - (k / p) ** alpha
    return [math.ceil(2.0**31 * (1.0 - (k / (j + 1)) ** alpha) / denom)
            for j in range(k, p - 1)]


def mean_cost(alpha: float, k: int, p: int) -> float:
    """The law's exact mean over the 2**31 draws."""
    edges = [0] + thresholds(alpha, k, p) + [2**31]
    return sum((hi - lo) * c for c, (lo, hi)
               in enumerate(zip(edges, edges[1:]), start=k)) / 2**31


def _walk(n_nodes: int, fanout: int, alpha: float, k: int, p: int):
    """Every node's chain, depth first from the root: the node count,
    the id sum, the cost sum and the final digests as ``uint32[n, 5]``."""
    key = (n_nodes, fanout, alpha, k, p)
    if key not in _CHAINS:
        sha1 = hashlib.sha1
        table = thresholds(alpha, k, p)
        index = [i.to_bytes(4, "big") for i in range(p)]
        finals = bytearray()
        nodes = id_sum = units = 0
        stack = [0]
        while stack:
            v = stack.pop()
            digest = sha1(bytes(16) + v.to_bytes(4, "big")).digest()
            c = k + bisect.bisect_right(
                table, int.from_bytes(digest[16:], "big") & 0x7FFFFFFF)
            for i in range(1, c):
                digest = sha1(digest + index[i]).digest()
            finals += digest
            nodes += 1
            id_sum += v
            units += c
            first = fanout * v + 1
            stack.extend(range(first, min(first + fanout, n_nodes)))
        words = np.frombuffer(bytes(finals), ">u4").reshape(-1, 5)
        _CHAINS[key] = (nodes, id_sum, units, words)
    return _CHAINS[key]


def reference(n_nodes: int, fanout: int, alpha: float, k: int, p: int,
              salt: int) -> dict:
    """``nodes``, ``id_sum`` (mod 2**32), ``units`` (the sum of the
    costs) and ``hash_sum`` (the salted checksum of the final digests)."""
    nodes, id_sum, units, words = _walk(n_nodes, fanout, alpha, k, p)
    return {"nodes": nodes, "id_sum": id_sum & _MASK32, "units": units,
            "hash_sum": uts.checksum(words, salt)}
