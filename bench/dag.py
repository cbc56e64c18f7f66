"""The Fig. 9 DAG: per-drain checksums and the plain reference.

The DAG is a complete ``fanout``-ary tree over the node ids
``0 .. n_nodes - 1``: node ``v``'s children are ``fanout * v + 1 ..
fanout * v + fanout``, those below ``n_nodes``.  Children are computed,
never stored, so a drain's backlog is the only state.

The worker body that drains it on the device is in
``drivers/dag_drain.py``; it sums the explored ids and their salted
hash per lane, and the reference here sums the same over a plain
breadth-first walk.
"""

from __future__ import annotations

import numpy as np

# Multiplier of the id hash (Knuth's multiplicative constant).
HASH_MUL = 0x9E3779B1
_MASK32 = 0xFFFFFFFF


def salt_for(seed: int) -> int:
    """The 32-bit salt of the id hash, drawn from the run's seed."""
    return int(np.random.default_rng(np.random.SeedSequence([seed, 9]))
               .integers(0, 2**32, dtype=np.uint64))


def _hash_np(ids: np.ndarray, salt: int) -> np.ndarray:
    h = (ids.astype(np.uint64) ^ np.uint64(salt)) * np.uint64(HASH_MUL)
    h &= np.uint64(_MASK32)
    return h ^ (h >> np.uint64(15))


def totals(carry) -> dict:
    """A carry read back to the host and summed over lanes (the checksums
    mod 2**32, as the device sums them)."""
    host = {k: np.asarray(v) for k, v in carry.items()}
    return {"explored": int(host["explored"].astype(np.int64).sum()),
            "id_sum": int(host["id_sum"].astype(np.uint64).sum()) & _MASK32,
            "hash_sum": int(host["hash_sum"].astype(np.uint64).sum())
            & _MASK32,
            "starved": int(host["starved"].astype(np.int64).sum())}


def reference(n_nodes: int, fanout: int, salt: int) -> dict:
    """The plain reference: a sequential breadth-first walk from the root,
    level by level, and the same sums over the ids it visits."""
    count, id_sum, hash_sum = 0, 0, 0
    level = np.zeros(1, np.int64)
    while level.size:
        count += int(level.size)
        id_sum = (id_sum + int(level.sum())) & _MASK32
        hash_sum = (hash_sum + int(_hash_np(level, salt).sum())) & _MASK32
        kids = (level[:, None] * fanout + 1
                + np.arange(fanout, dtype=np.int64)[None, :]).reshape(-1)
        level = kids[kids < n_nodes]
    return {"explored": count, "id_sum": id_sum, "hash_sum": hash_sum}
