"""The Fig. 9 DAG with heavy-tailed per-node cost, on the steal runtime.

The tree is the Fig. 9 DAG's: the complete ``fanout``-ary tree over the
node ids ``0 .. n_nodes - 1``, node ``v``'s children ``fanout * v + 1 ..
fanout * v + fanout``, those below ``n_nodes``.  Each node costs ``c(v)``
work units, and one unit is one SHA-1 compression, as UTS's ``-g``
granularity counts a node's work (Olivier et al., LCPC 2006):

* the first unit of node ``v`` is UTS's ``rng_init(v)``, digest ``h_0``;
* unit ``i`` (``i = 1 .. c(v) - 1``) is ``h_i = rng_spawn(h_{i-1}, i)``;
* the node's final digest ``h_{c(v)-1}`` enters a salted checksum
  (:func:`repro.core.uts.node_hash`).

The cost law is a Bounded Pareto (Harchol-Balter, Crovella and Murta,
JPDC 59(2), 1999): ``c(v) = floor(X)``, ``X ~ BoundedPareto(k, p,
alpha)``, drawn from ``h_0``'s UTS draw (31 bits) through an integer
threshold table (:class:`ParetoCost`), so the device and the reference
agree exactly.  The costs depend on the node ids alone.

A drain runs through the system's normal path, as the DAG drain does:
:func:`repro.distributed.launch_runtime` builds the lanes, the root goes
to one lane, and :meth:`repro.runtime.StealRuntime.run_fused`
(``until_drained=True``) drives the worker body below and the master's
steals until every queue is empty and nothing is in flight.  A queue
item is a node id, 4 bytes.

The worker body (:func:`make_body`) keeps ``slots`` nodes in flight a
lane, the continuous-batching pattern of ``serve/decode.py`` applied to
a scheduler's nodes.  Each round it pops as many nodes as it has free
slots (``irregular.refill``), advances every busy slot by one
compression in one batched :func:`repro.core.uts.sha1_block`
(``irregular.work``; a new slot's block holds ``rng_init``'s words, a
continuing one's ``rng_spawn``'s), then retires the nodes whose cost is
spent, pushes their children and frees their slots
(``irregular.retire``).  Its carry reports the nodes it holds as the
runtime's ``in_flight`` leaf, so a drain does not end while a lane still
works on a node (DESIGN.md §12).

:func:`reference_drain` is the plain reference: a sequential walk with
:mod:`hashlib`.
"""

from __future__ import annotations

import bisect
import hashlib
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import StealPolicy
from repro.core.uts import _hash_np, draw, node_hash, sha1_block
from repro.obs import spans
from repro.runtime.executor import IN_FLIGHT

__all__ = ["ITEM", "IrregularDrain", "ParetoCost", "make_body",
           "reference_drain", "zero_carry"]

# A queue item: the node id.
ITEM = jax.ShapeDtypeStruct((), jnp.int32)

_PAD = 0x80000000   # the padding's leading 1 bit, as a big-endian word
_MASK32 = 0xFFFFFFFF
_POS_MASK = 0x7FFFFFFF


class ParetoCost(NamedTuple):
    """A Bounded Pareto cost law on ``[k, p)``, floored to whole units.

    ``c = k + #{j in k .. p-2 : draw >= T[j]}`` for a 31-bit uniform
    ``draw``, with ``T[j] = ceil(2**31 * F(j + 1))`` and ``F(x) = (1 -
    (k/x)**alpha) / (1 - (k/p)**alpha)``, the law's CDF, in float64: so
    ``P(c > j) = P(draw >= T[j]) = 1 - F(j + 1) = P(X >= j + 1)``, and
    ``c = floor(X)`` ranges over ``k .. p - 1``."""

    alpha: float
    k: int
    p: int

    def cdf(self, x) -> np.ndarray:
        """The law's CDF ``F(x)``, in float64."""
        x = np.asarray(x, np.float64)
        return ((1.0 - (self.k / x) ** self.alpha)
                / (1.0 - (self.k / self.p) ** self.alpha))

    def table(self) -> np.ndarray:
        """The thresholds ``T[k] .. T[p-2]`` (ascending, ``uint32``)."""
        j = np.arange(self.k, self.p - 1, dtype=np.float64)
        return np.ceil(2.0**31 * self.cdf(j + 1)).astype(np.uint32)

    def cost(self, draws) -> jnp.ndarray:
        """The cost of each 31-bit draw (``int32``), on the device."""
        table = jnp.asarray(self.table())
        return self.k + jnp.searchsorted(
            table, jnp.asarray(draws, jnp.uint32), side="right",
            method="compare_all").astype(jnp.int32)


# ---------------------------------------------------------------------------
# The worker body
# ---------------------------------------------------------------------------


def make_body(n_nodes: int, fanout: int, cost: ParetoCost, slots: int,
              salt: int, ops):
    """The per-lane worker body ``(q, carry) -> (q, carry)`` of a pool of
    ``slots`` nodes in flight (module docstring)."""
    n_nodes, fanout, slots = int(n_nodes), int(fanout), int(slots)
    salt = int(salt) & _MASK32
    offsets = jnp.arange(1, fanout + 1, dtype=jnp.int32)
    u32 = jnp.uint32
    zero = u32(0)

    def body(q, carry):
        held = carry["left"] > 0
        free = ~held
        n_free = slots - jnp.sum(held.astype(jnp.int32))
        with jax.named_scope("irregular.refill"):
            starved = ((q.size == 0) & (n_free > 0)).astype(jnp.int32)
            q, items, n_popped = ops.pop_bulk(q, slots, n_free)
            # The i-th popped node takes the i-th free slot.
            rank = jnp.cumsum(free.astype(jnp.int32)) - 1
            new = free & (rank < n_popped)
            ids = jnp.where(new, items[jnp.clip(rank, 0, slots - 1)],
                            carry["id"])
        with jax.named_scope("irregular.work"):
            d = carry["digest"]
            step = carry["step"]
            # One block a slot: rng_init(id)'s words for a new node,
            # rng_spawn(digest, step)'s for a continuing one.
            words = ([jnp.where(new, zero, d[:, i]) for i in range(4)]
                     + [jnp.where(new, ids.astype(u32), d[:, 4]),
                        jnp.where(new, u32(_PAD), step.astype(u32)),
                        jnp.where(new, zero, u32(_PAD))]
                     + [zero] * 8 + [jnp.where(new, u32(160), u32(192))])
            working = new | held
            digest = jnp.where(working[:, None], sha1_block(words), d)
            left = jnp.where(new, cost.cost(draw(digest)) - 1,
                             carry["left"] - held.astype(jnp.int32))
            step = jnp.where(new, 1, step + held.astype(jnp.int32))
        with jax.named_scope("irregular.retire"):
            done = working & (left == 0)
            kids = jnp.clip(n_nodes - 1 - fanout * ids, 0, fanout)
            parent = done & (kids > 0)
            # Parents with every child first, then the one node with
            # some, so the live children are a prefix of the batch.
            key = jnp.where(parent, jnp.where(kids == fanout, 0, 1), 2)
            order = jnp.argsort(key, stable=True)
            children = (ids[order][:, None] * fanout + offsets).reshape(-1)
            q, _ = ops.push(q, children,
                            jnp.sum(jnp.where(done, kids, 0)))
        as_u32 = lambda x: jnp.sum(jnp.where(done, x, zero))
        return q, {
            "id": ids,
            "digest": digest,
            "left": left,
            "step": step,
            "explored": carry["explored"] + jnp.sum(done.astype(jnp.int32)),
            "id_sum": carry["id_sum"] + as_u32(ids.astype(u32)),
            "hash_sum": carry["hash_sum"] + as_u32(node_hash(digest, salt)),
            "units": carry["units"] + jnp.sum(jnp.where(done, step, 0)),
            "busy_slot_rounds": carry["busy_slot_rounds"]
            + jnp.sum(working.astype(jnp.int32)),
            "starved": carry["starved"] + starved,
            IN_FLIGHT: jnp.sum((left > 0).astype(jnp.int32)),
        }

    return body


def zero_carry(lanes: int, slots: int) -> Dict[str, jnp.ndarray]:
    """Empty slots and zero counts, ``lanes`` lanes of ``slots`` slots."""
    i32 = lambda *s: jnp.zeros((lanes,) + s, jnp.int32)
    u32 = lambda *s: jnp.zeros((lanes,) + s, jnp.uint32)
    return {"id": i32(slots), "digest": u32(slots, 5), "left": i32(slots),
            "step": i32(slots), "explored": i32(), "id_sum": u32(),
            "hash_sum": u32(), "units": i32(), "busy_slot_rounds": i32(),
            "starved": i32(), IN_FLIGHT: i32()}


# ---------------------------------------------------------------------------
# Drains
# ---------------------------------------------------------------------------


class IrregularDrain:
    """Drains of one costed DAG on one runtime: the lanes, the worker body
    and the seeding are built once, and each drain starts from empty
    queues and empty slots.

    ``seed(lane)`` pushes the root to ``lane`` (span ``irregular:seed``,
    counter ``irregular.drains``); ``dispatch(carry)`` runs up to
    ``fused_rounds`` rounds in one ``run_fused`` block that stops when
    every queue is empty and no slot holds a node; ``totals(carry)``
    reads a drain's counts back (counter ``irregular.units``)."""

    def __init__(self, n_nodes: int, n_workers: int, *, fanout: int = 4,
                 cost: ParetoCost = ParetoCost(1.1, 1, 1024),
                 slots: int = 512, ring_slots: int = 16_384,
                 policy: Optional[StealPolicy] = None,
                 backend: str = "auto", fused_rounds: int = 8,
                 salt: int = 0, execution: str = "vmap"):
        from repro.distributed.launch import launch_runtime

        self.n_nodes, self.fanout = int(n_nodes), int(fanout)
        self.cost, self.slots = cost, int(slots)
        self.salt = int(salt) & _MASK32
        self.fused_rounds = int(fused_rounds)
        self.rt = launch_runtime(n_workers, ring_slots, ITEM,
                                 execution=execution, backend=backend,
                                 max_pop=self.slots,
                                 policy=policy or StealPolicy())
        self.body = make_body(self.n_nodes, self.fanout, cost, self.slots,
                              self.salt, self.rt.ops)

    def zero_carry(self) -> Dict[str, jnp.ndarray]:
        return zero_carry(self.rt.n_workers, self.slots)

    def seed(self, lane: int) -> None:
        """Start a drain: the root on ``lane``."""
        with spans.span("irregular:seed"):
            spans.count("irregular.drains")
            self.rt.push(lane, jnp.zeros((1,), jnp.int32), 1)

    def dispatch(self, carry):
        """One fused block; returns ``(carry, rounds run)``."""
        carry, _stats, rounds = self.rt.run_fused(
            self.fused_rounds, self.body, carry, until_drained=True)
        return carry, rounds

    def totals(self, carry) -> dict:
        """A drain's counts so far, summed over lanes (the sums mod
        2**32, as the device sums them): ``nodes`` retired, ``id_sum``,
        ``hash_sum``, ``units`` (the costs of the retired nodes),
        ``busy_slot_rounds`` (compressions done), ``starved`` and
        ``in_flight``."""
        host = {k: np.asarray(carry[k]) for k in (
            "explored", "id_sum", "hash_sum", "units", "busy_slot_rounds",
            "starved", IN_FLIGHT)}
        total = lambda k: int(host[k].astype(np.int64).sum())
        out = {"nodes": total("explored"),
               "id_sum": int(host["id_sum"].astype(np.uint64).sum())
               & _MASK32,
               "hash_sum": int(host["hash_sum"].astype(np.uint64).sum())
               & _MASK32,
               **{k: total(k) for k in ("units", "busy_slot_rounds",
                                        "starved", IN_FLIGHT)}}
        spans.count("irregular.units", out["units"])
        return out

    def run(self, lane: int) -> dict:
        """One whole drain from ``lane``: its totals, the rounds it ran
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


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def reference_drain(n_nodes: int, fanout: int, cost: ParetoCost,
                    salt: int = 0) -> dict:
    """The plain reference: a sequential depth-first walk from the root
    that does each node's units with :mod:`hashlib`.  Returns ``nodes``,
    ``id_sum``, ``units`` (the sum of the costs) and ``hash_sum`` (the
    salted checksum of the final digests), the sums mod 2**32 but
    ``units``."""
    sha1 = hashlib.sha1
    thresholds = [int(t) for t in cost.table()]
    index = [i.to_bytes(4, "big") for i in range(cost.p)]
    finals = bytearray()
    id_sum = units = 0
    stack = [0]
    while stack:
        v = stack.pop()
        digest = sha1(bytes(16) + v.to_bytes(4, "big")).digest()
        c = cost.k + bisect.bisect_right(
            thresholds, int.from_bytes(digest[16:], "big") & _POS_MASK)
        for i in range(1, c):
            digest = sha1(digest + index[i]).digest()
        finals += digest
        id_sum += v
        units += c
        first = fanout * v + 1
        stack.extend(range(first, min(first + fanout, n_nodes)))
    words = np.frombuffer(bytes(finals), ">u4").reshape(-1, 5)
    return {"nodes": int(words.shape[0]), "id_sum": id_sum & _MASK32,
            "units": units,
            "hash_sum": int(_hash_np(words, salt).sum()) & _MASK32}

