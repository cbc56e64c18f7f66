"""UTS, the Unbalanced Tree Search benchmark, on the steal runtime.

UTS (Olivier, Huan, Liu, Prins, Dinan, Sadayappan, Tseng, "UTS: An
Unbalanced Tree Search Benchmark", LCPC 2006) is the field's standard
load for work-stealing schedulers.  Its trees are generated, never
stored: a node is a 20-byte SHA-1 digest, and every rule below is UTS's
own (``rng_init``, ``rng_spawn``, ``rng_rand`` and the binomial child
rule of its C sources):

* the root is ``SHA1(16 zero bytes || r)``, ``r`` as 4 bytes big-endian;
* child ``i`` of a node is ``SHA1(parent's 20 bytes || i)``, ``i`` as 4
  bytes big-endian;
* a node's draw is its digest's bytes 16..19, big-endian, ``&
  0x7fffffff``;
* in a binomial tree the root has ``b0`` children, and every other node
  has ``m`` children where ``draw / 2**31 < q`` and none otherwise.

Here a digest is five ``uint32`` words (the digest's bytes, big-endian),
and a SHA-1 of one padded 64-byte block is 80 rounds of 32-bit adds,
rotates and logic ops on them (:func:`sha1_block`), computed for a whole
batch on the device.  The child test is integer: ``draw < threshold``,
:attr:`BinomialTree.threshold` being the least integer not below
``q * 2**31``, which is UTS's float test exactly for integer draws.

A search runs through the system's normal path, as the DAG drain does:
:func:`repro.distributed.launch_runtime` builds the lanes, the root's
children go to one lane, and :meth:`repro.runtime.StealRuntime.run_fused`
(``until_drained=True``) drives the worker body below and the master's
steals until every queue is empty.  A queue item is a node: its five
digest words as ``int32`` bit patterns and its height, 24 bytes.  The
worker body pops a bulk, spawns ``m`` children of every popped node that
has them, compacts them to the front and pushes them.  It counts, per
lane, the nodes explored, the leaves, the largest height, a salted
checksum of the explored digests (:func:`node_hash`) and the rounds the
lane began with an empty queue.

:func:`reference_search` is the plain reference: a sequential
depth-first walk with :mod:`hashlib`.
"""

from __future__ import annotations

import hashlib
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.policy import StealPolicy
from repro.kernels.ring_tile import DEFAULT_BLOCK
from repro.obs import spans

__all__ = ["BinomialTree", "ITEM", "UTSSearch", "make_body", "node_hash",
           "reference_search", "rng_init", "rng_spawn", "sha1_block",
           "uts_search"]

# A queue item: digest words 0..4 as int32 bit patterns, then the height.
ITEM = jax.ShapeDtypeStruct((6,), jnp.int32)

_H0 = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0)
_K = (0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xCA62C1D6)
_PAD = 0x80000000   # the padding's leading 1 bit, as a big-endian word
_POS_MASK = 0x7FFFFFFF
# Multiplier of the checksum's mixing (Knuth's multiplicative constant).
HASH_MUL = 0x9E3779B1
_MASK32 = 0xFFFFFFFF


class BinomialTree(NamedTuple):
    """A UTS binomial tree: ``b0`` root children, ``m`` children with
    probability ``q`` below the root, root seed ``root_seed`` (UTS's
    ``-b``, ``-m``, ``-q``, ``-r``)."""

    b0: int
    q: float
    m: int
    root_seed: int

    @property
    def threshold(self) -> int:
        """A node below the root has children iff ``draw < threshold``:
        UTS tests ``draw / 2**31 < q`` in doubles, where the division is
        exact, so this is the least integer not below ``q * 2**31``."""
        return math.ceil(self.q * 2**31)


# ---------------------------------------------------------------------------
# SHA-1 over uint32 words
# ---------------------------------------------------------------------------


def _u32(x: int) -> jnp.ndarray:
    return jnp.uint32(x)


def _rotl(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x << _u32(n)) | (x >> _u32(32 - n))


# Each stage's 20 rounds share one logic function and one constant.
_F = (lambda b, c, d: (b & c) | (~b & d),
      lambda b, c, d: b ^ c ^ d,
      lambda b, c, d: (b & c) | (b & d) | (c & d),
      lambda b, c, d: b ^ c ^ d)
# Rounds unrolled in each stage's loop: the whole 80 unrolled make a
# program that XLA compiles slowly (16 s on a CPU for one spawn).
_UNROLL = 5


def sha1_block(words) -> jnp.ndarray:
    """SHA-1 of messages that fit one padded block: ``words`` are the
    block's 16 big-endian ``uint32`` words (arrays or Python ints that
    broadcast together).  Returns the digests' five words stacked on a
    last axis.

    The message schedule rides in the loop as a window of 16 words: round
    ``t`` reads ``W[t]`` from its front and appends ``W[t + 16]``."""
    shape = jnp.broadcast_shapes(*(jnp.shape(w) for w in words))
    w = tuple(jnp.broadcast_to(jnp.asarray(x, jnp.uint32), shape)
              for x in words)
    state = tuple(jnp.full(shape, h, jnp.uint32) for h in _H0)
    for f, k in zip(_F, _K):
        def one_round(_, carry, f=f, k=_u32(k)):
            (a, b, c, d, e), w = carry
            tmp = _rotl(a, 5) + f(b, c, d) + e + k + w[0]
            nxt = _rotl(w[13] ^ w[8] ^ w[2] ^ w[0], 1)
            return (tmp, a, _rotl(b, 30), c, d), w[1:] + (nxt,)

        state, w = lax.fori_loop(0, 20, one_round, (state, w),
                                 unroll=_UNROLL)
    return jnp.stack([x + _u32(h) for x, h in zip(state, _H0)], axis=-1)


def rng_init(seed: int) -> jnp.ndarray:
    """UTS's root state: SHA-1 of 16 zero bytes and ``seed`` (20 bytes,
    160 bits).  Returns the digest's five words, ``uint32[5]``."""
    words = [0, 0, 0, 0, seed & _MASK32, _PAD] + [0] * 9 + [160]
    return sha1_block(words)


def rng_spawn(parents: jnp.ndarray, index: jnp.ndarray) -> jnp.ndarray:
    """UTS's child states: SHA-1 of each parent's digest and its spawn
    index (24 bytes, 192 bits).  ``parents`` is ``uint32[..., 5]``,
    ``index`` broadcasts against ``parents[..., 0]``; returns
    ``uint32[..., 5]``."""
    words = ([parents[..., k] for k in range(5)]
             + [jnp.asarray(index, jnp.uint32), _PAD] + [0] * 8 + [192])
    return sha1_block(words)


def draw(digests: jnp.ndarray) -> jnp.ndarray:
    """UTS's ``rng_rand``: bytes 16..19 of each digest, ``& 0x7fffffff``."""
    return digests[..., 4] & _u32(_POS_MASK)


def node_hash(digests: jnp.ndarray, salt: int) -> jnp.ndarray:
    """The checksum's term of each node: the five words folded into a
    salted 32-bit mix (``uint32[...]``)."""
    x = jnp.full(digests.shape[:-1], salt & _MASK32, jnp.uint32)
    for k in range(5):
        x = (x ^ digests[..., k]) * _u32(HASH_MUL)
        x = x ^ (x >> _u32(15))
    return x


# ---------------------------------------------------------------------------
# The worker body
# ---------------------------------------------------------------------------


def _as_items(digests: jnp.ndarray, heights: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate(
        [lax.bitcast_convert_type(digests, jnp.int32), heights[:, None]],
        axis=1)


def make_body(tree: BinomialTree, pop_batch: int, salt: int, ops):
    """The per-lane worker body ``(q, carry) -> (q, carry)``: pop up to
    ``pop_batch`` nodes, spawn ``tree.m`` children of each that has
    them, compact those parents' children to the front, push them."""
    m = int(tree.m)
    threshold = _u32(tree.threshold)
    index = jnp.tile(jnp.arange(m, dtype=jnp.uint32), pop_batch)
    zero = jnp.uint32(0)

    def body(q, carry):
        starved = (q.size == 0).astype(jnp.int32)
        q, items, n_popped = ops.pop_bulk(q, pop_batch, jnp.int32(pop_batch))
        valid = jnp.arange(pop_batch, dtype=jnp.int32) < n_popped
        digests = lax.bitcast_convert_type(items[:, :5], jnp.uint32)
        heights = items[:, 5]
        parent = valid & (draw(digests) < threshold)
        order = jnp.argsort(~parent, stable=True)  # parents to the front
        kids = rng_spawn(jnp.repeat(digests[order], m, axis=0), index)
        q, _ = ops.push(q, _as_items(kids, jnp.repeat(heights[order] + 1, m)),
                        m * jnp.sum(parent.astype(jnp.int32)))
        return q, {
            "explored": carry["explored"] + jnp.sum(valid.astype(jnp.int32)),
            "leaves": carry["leaves"]
            + jnp.sum((valid & ~parent).astype(jnp.int32)),
            "max_height": jnp.maximum(carry["max_height"],
                                      jnp.max(jnp.where(valid, heights, 0))),
            "hash_sum": carry["hash_sum"]
            + jnp.sum(jnp.where(valid, node_hash(digests, salt), zero)),
            "starved": carry["starved"] + starved,
        }

    return body


def zero_carry(lanes: int) -> Dict[str, jnp.ndarray]:
    return {"explored": jnp.zeros((lanes,), jnp.int32),
            "leaves": jnp.zeros((lanes,), jnp.int32),
            "max_height": jnp.zeros((lanes,), jnp.int32),
            "hash_sum": jnp.zeros((lanes,), jnp.uint32),
            "starved": jnp.zeros((lanes,), jnp.int32)}


def _root_items(tree: BinomialTree, rows: int, salt: int):
    """The root's ``rows`` first children as queue items, height 1, and
    the root's checksum term."""
    root = rng_init(tree.root_seed)
    kids = rng_spawn(jnp.broadcast_to(root, (rows, 5)),
                     jnp.arange(rows, dtype=jnp.uint32))
    return (_as_items(kids, jnp.ones((rows,), jnp.int32)),
            node_hash(root, salt))


# ---------------------------------------------------------------------------
# Searches
# ---------------------------------------------------------------------------


class UTSSearch:
    """Searches of one tree on one runtime: the lanes, the worker body
    and the seeding are built once, and each search starts from empty
    queues.

    ``seed(lane)`` pushes the root's children to ``lane`` (span
    ``uts:seed``, counter ``uts.searches``); ``dispatch(carry)`` runs up
    to ``fused_rounds`` rounds in one ``run_fused`` block that stops when
    every queue is empty; ``totals(carry)`` reads a search's counts back,
    the root included (explored, height 0, in the checksum)."""

    def __init__(self, tree: BinomialTree, n_workers: int, *,
                 ring_slots: int = 16_384, pop_batch: int = 64,
                 policy: Optional[StealPolicy] = None,
                 backend: str = "auto", fused_rounds: int = 8,
                 salt: int = 0):
        from repro.distributed.launch import launch_runtime

        # The seeding push is padded to whole kernel blocks, which the
        # queue kernels' geometry needs; only b0 rows are pushed.
        self._seed_rows = max(1, -(-tree.b0 // DEFAULT_BLOCK)) * DEFAULT_BLOCK
        if self._seed_rows + DEFAULT_BLOCK > ring_slots:
            raise ValueError(f"b0={tree.b0} root children do not fit a "
                             f"ring of {ring_slots} slots")
        self.tree = tree
        self.salt = int(salt) & _MASK32
        self.fused_rounds = int(fused_rounds)
        self.rt = launch_runtime(n_workers, ring_slots, ITEM,
                                 execution="vmap", backend=backend,
                                 max_pop=pop_batch,
                                 policy=policy or StealPolicy())
        self.body = make_body(tree, pop_batch, self.salt, self.rt.ops)
        self._root = jax.jit(_root_items, static_argnums=(0, 1, 2))
        self._root_hash = None

    def zero_carry(self) -> Dict[str, jnp.ndarray]:
        return zero_carry(self.rt.n_workers)

    def seed(self, lane: int) -> None:
        """Start a search: the root's ``b0`` children on ``lane``."""
        with spans.span("uts:seed"):
            spans.count("uts.searches")
            items, root_hash = self._root(self.tree, self._seed_rows,
                                          self.salt)
            self.rt.push(lane, items, self.tree.b0)
            self._root_hash = root_hash

    def dispatch(self, carry):
        """One fused block; returns ``(carry, rounds run)``."""
        carry, _stats, rounds = self.rt.run_fused(
            self.fused_rounds, self.body, carry, until_drained=True)
        return carry, rounds

    def totals(self, carry) -> dict:
        """A search's counts so far, summed over lanes (the checksum mod
        2**32, as the device sums it), the root included."""
        host = {k: np.asarray(v) for k, v in carry.items()}
        hash_sum = (int(host["hash_sum"].astype(np.uint64).sum())
                    + int(self._root_hash))
        return {"nodes": 1 + int(host["explored"].astype(np.int64).sum()),
                "leaves": int(host["leaves"].astype(np.int64).sum())
                + (self.tree.b0 == 0),
                "depth": int(host["max_height"].max()),
                "hash_sum": hash_sum & _MASK32,
                "starved": int(host["starved"].astype(np.int64).sum())}

    def run(self, lane: int) -> dict:
        """One whole search from ``lane``: its totals, the rounds it ran
        and the queue sizes it left."""
        self.seed(lane)
        carry, total = self.zero_carry(), 0
        while True:
            carry, rounds = self.dispatch(carry)
            total += rounds
            if rounds < self.fused_rounds:
                break
        out = self.totals(carry)
        out["rounds"] = total
        out["final_sizes"] = [int(x) for x in self.rt.sizes()]
        return out


def uts_search(tree: BinomialTree, n_workers: int, *,
               ring_slots: int = 16_384, pop_batch: int = 64,
               policy: Optional[StealPolicy] = None, backend: str = "auto",
               fused_rounds: int = 8, root_lane: int = 0,
               salt: int = 0) -> dict:
    """Search ``tree`` on ``n_workers`` lanes, the root's children
    starting on ``root_lane``.  Returns ``nodes``, ``leaves``, ``depth``
    (the largest height), ``hash_sum`` (the salted checksum), ``starved``
    lane-rounds, ``rounds`` and ``final_sizes``."""
    search = UTSSearch(tree, n_workers, ring_slots=ring_slots,
                       pop_batch=pop_batch, policy=policy, backend=backend,
                       fused_rounds=fused_rounds, salt=salt)
    return search.run(root_lane)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def _hash_np(words: np.ndarray, salt: int) -> np.ndarray:
    x = np.full(words.shape[:-1], salt & _MASK32, np.uint64)
    for k in range(5):
        x = ((x ^ words[..., k].astype(np.uint64)) * HASH_MUL) & _MASK32
        x ^= x >> np.uint64(15)
    return x


def reference_search(tree: BinomialTree, salt: int = 0) -> dict:
    """The plain reference: UTS's rules as its C sources apply them, in
    a sequential depth-first walk with :mod:`hashlib`.  Returns
    ``nodes``, ``leaves``, ``depth`` and ``hash_sum`` (the checksum of
    :func:`node_hash`, mod 2**32)."""
    sha1 = hashlib.sha1
    index = [i.to_bytes(4, "big") for i in range(max(tree.b0, tree.m))]
    root = sha1(bytes(16) + (tree.root_seed & _MASK32).to_bytes(4, "big")
                ).digest()
    seen = bytearray(root)
    leaves, depth = int(tree.b0 == 0), 0
    stack = [(sha1(root + index[i]).digest(), 1) for i in range(tree.b0)]
    while stack:
        digest, height = stack.pop()
        seen += digest
        depth = max(depth, height)
        if (int.from_bytes(digest[16:], "big") & _POS_MASK) < tree.threshold:
            base = sha1(digest)
            for i in range(tree.m):
                child = base.copy()
                child.update(index[i])
                stack.append((child.digest(), height + 1))
        else:
            leaves += 1
    words = np.frombuffer(bytes(seen), ">u4").reshape(-1, 5)
    return {"nodes": int(words.shape[0]), "leaves": leaves, "depth": depth,
            "hash_sum": int(_hash_np(words, salt).sum()) & _MASK32}
