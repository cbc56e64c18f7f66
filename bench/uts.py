"""UTS binomial trees: the plain reference and the checksum's salt.

A tree of UTS (Olivier et al., "UTS: An Unbalanced Tree Search
Benchmark", LCPC 2006) is generated from its parameters by UTS's own
rules, as its C sources apply them:

* the root is ``SHA1(16 zero bytes || r)``, ``r`` as 4 bytes big-endian;
* child ``i`` of a node is ``SHA1(parent's 20 bytes || i)``, ``i`` as 4
  bytes big-endian;
* a node's draw is its digest's bytes 16..19, big-endian, ``&
  0x7fffffff``, and ``draw / 2**31`` is compared with ``q`` in doubles;
* the root has ``b0`` children; every other node has ``m`` children
  where the draw is below ``q`` and none otherwise.

:func:`reference` walks the tree depth first with :mod:`hashlib` and
returns its nodes, leaves, depth and a salted checksum of every node's
digest.  The checksum folds the digest's five big-endian 32-bit words
into one: ``x = salt``, then for each word ``x = ((x ^ word) *
0x9E3779B1) mod 2**32`` and ``x ^= x >> 15``; the terms are summed mod
2**32.  The program under test computes the same sum on the device.
This module imports nothing of the program.
"""

from __future__ import annotations

import hashlib

import numpy as np

HASH_MUL = 0x9E3779B1
_MASK32 = 0xFFFFFFFF


def salt_for(seed: int) -> int:
    """The 32-bit salt of the checksum, drawn from the run's seed."""
    return int(np.random.default_rng(np.random.SeedSequence([seed, 15]))
               .integers(0, 2**32, dtype=np.uint64))


def checksum(words: np.ndarray, salt: int) -> int:
    """The salted checksum of digests given as ``uint32[n, 5]`` words."""
    x = np.full(words.shape[0], salt & _MASK32, np.uint64)
    for k in range(5):
        x = ((x ^ words[:, k].astype(np.uint64)) * np.uint64(HASH_MUL)
             ) & np.uint64(_MASK32)
        x ^= x >> np.uint64(15)
    return int(x.sum()) & _MASK32


def reference(b0: int, q: float, m: int, r: int, salt: int) -> dict:
    """A sequential depth-first walk of the binomial tree ``(b0, q, m,
    r)``: ``nodes``, ``leaves``, ``depth`` (the largest height, the root
    at 0) and ``hash_sum`` (the salted checksum)."""
    sha1 = hashlib.sha1
    index = [i.to_bytes(4, "big") for i in range(max(b0, m))]
    root = sha1(bytes(16) + (r & _MASK32).to_bytes(4, "big")).digest()
    digests = bytearray(root)
    leaves, depth = int(b0 == 0), 0
    stack = [(sha1(root + index[i]).digest(), 1) for i in range(b0)]
    while stack:
        digest, height = stack.pop()
        digests += digest
        depth = max(depth, height)
        draw = int.from_bytes(digest[16:], "big") & 0x7FFFFFFF
        if draw / 2147483648.0 < q:
            parent = sha1(digest)
            for i in range(m):
                child = parent.copy()
                child.update(index[i])
                stack.append((child.digest(), height + 1))
        else:
            leaves += 1
    words = np.frombuffer(bytes(digests), ">u4").reshape(-1, 5)
    return {"nodes": int(words.shape[0]), "leaves": leaves, "depth": depth,
            "hash_sum": checksum(words, salt)}
