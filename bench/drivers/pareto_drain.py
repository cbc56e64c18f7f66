"""``pareto_drain``: a closed loop of Fig. 9 DAG drains with heavy-tailed
per-node cost.

Back-to-back drains of one costed DAG on one runtime, through the
program's own drain (``repro.core.irregular.IrregularDrain``: the lanes
from ``launch_runtime``, the worker body that keeps a pool of nodes in
flight a lane and does one SHA-1 compression a busy slot a round, the
root seeded on lane ``seed mod lanes``), dispatched as
``StealRuntime.run`` dispatches: ``run_fused(k, until_drained=True)``
until a block comes back short.  A block ends a drain only when every
queue is empty and no slot holds a node.  Nodes are counted when they
retire, at every dispatch boundary, so a drain cut by the window's end
counts as far as it got.

Every drain is compared with the plain reference (``bench/pareto.py``,
a ``hashlib`` walk that imports nothing of the program): the node
count, the sums of the ids and of the costs, and the reference's salted
checksum of the nodes' final digests.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

from bench import pareto
from bench.jobs import Check, annotate

ITEM_BYTES = 4  # a node id


def tree_depth(n_nodes: int, fanout: int) -> int:
    """The depth of the complete ``fanout``-ary tree of ``n_nodes``."""
    depth, v = 0, n_nodes - 1
    while v > 0:
        v = (v - 1) // fanout
        depth += 1
    return depth


class Driver:
    """Closed loop of costed Fig. 9 DAG drains."""

    def __init__(self, config: dict, traffic: dict, layout: dict, seed: int):
        cost = config["cost"]
        if (cost["law"], cost["rounding"]) != ("bounded_pareto", "floor"):
            raise ValueError(f"no such cost law: {cost}")
        self.law = (float(cost["alpha"]), int(cost["k"]), int(cost["p"]))
        self.n_nodes = int(config["nodes"])
        self.fanout = int(config["fanout"])
        self.slots = int(config["slots"])
        self.ring = int(config["ring_slots"])
        self.fused = int(config["fused_rounds"])
        self.backend = config["backend"]
        self.policy = dict(config["policy"])
        self.lanes = int(layout["lanes"])
        self.execution = layout["execution"]
        self.salt = pareto.salt_for(seed)
        self.root_lane = seed % self.lanes
        # A drain that has not ended after this many rounds has lost its
        # way: a sound one takes about its units over the slots, plus a
        # last node's cost and the tree's depth.
        units = self.n_nodes * pareto.mean_cost(*self.law)
        self.max_rounds = int(traffic["max_rounds_factor"]) * (
            math.ceil(units / (self.lanes * self.slots)) + self.law[2] - 1
            + tree_depth(self.n_nodes, self.fanout))
        self.drains: List[dict] = []
        self.drain = None

    # -- building blocks ----------------------------------------------------

    def _dispatch(self, carry):
        with annotate("run_fused"):
            return self.drain.dispatch(carry)

    def setup(self) -> None:
        import jax

        from repro.core.irregular import IrregularDrain, ParetoCost
        from repro.core.policy import StealPolicy

        self.drain = IrregularDrain(
            self.n_nodes, self.lanes, fanout=self.fanout,
            cost=ParetoCost(*self.law), slots=self.slots,
            ring_slots=self.ring, policy=StealPolicy(**self.policy),
            backend=self.backend, fused_rounds=self.fused, salt=self.salt,
            execution=self.execution)
        # Warm up: the fused block on empty lanes (it exits before its
        # first round), twice so that both the fresh carry and a carry
        # the block returned have been seen, then the seeding push.
        carry = self.drain.zero_carry()
        for _ in range(2):
            carry, _ = self._dispatch(carry)
        jax.block_until_ready(carry)
        self.drain.seed(self.root_lane)
        jax.block_until_ready(self.drain.rt.queues)

    # -- the window ----------------------------------------------------------

    def _close(self, carry, rounds: int) -> None:
        out = self.drain.totals(carry)
        out["rounds"] = rounds
        out["final_sizes"] = [int(x) for x in self.drain.rt.sizes()]
        self.drains.append(out)

    def window(self, seconds: float, clock: Callable[[], float],
               traced: bool = False) -> dict:
        del traced  # the counts below come from the worker body's carry
        records = self.drain.rt.telemetry.rounds
        tele0 = len(records)
        carry = self.drain.zero_carry()
        rounds, drain_rounds = 0, 0
        closed0 = len(self.drains)
        starts = set()  # the window's rounds that began a drain
        t0 = clock()
        deadline = t0 + seconds
        with annotate("window"):
            while True:
                carry, r = self._dispatch(carry)
                rounds += r
                drain_rounds += r
                if r < self.fused or drain_rounds >= self.max_rounds:
                    self._close(carry, drain_rounds)
                    carry, drain_rounds = self.drain.zero_carry(), 0
                    self.drain.seed(self.root_lane)
                    starts.add(rounds)
                t1 = clock()
                if t1 >= deadline:
                    break
        self._carry, self._drain_rounds = carry, drain_rounds
        closed = self.drains[closed0:]
        parts = closed + [self.drain.totals(carry)]
        total = lambda key: sum(d[key] for d in parts)
        window = records[tele0:]
        # A round began with every queue empty where the round before it,
        # of the same drain, left them so: only work in flight ran it.
        inflight_only = sum(
            1 for i in range(1, len(window))
            if window[i - 1].sizes_total == 0 and i not in starts)
        elapsed = t1 - t0
        return {
            "e2e": {"nodes_per_s": total("nodes") / elapsed},
            "elapsed_s": elapsed,
            "counters": {
                "rounds": rounds,
                "lane_rounds": rounds * self.lanes,
                "starved_lane_rounds": total("starved"),
                "popped": total("nodes") + total("in_flight"),
                "transferred": sum(rec.n_transferred for rec in window),
                "busy_slot_rounds": total("busy_slot_rounds"),
                "slot_rounds": rounds * self.lanes * self.slots,
                "inflight_only_rounds": inflight_only,
                "units": total("units"),
                "item_bytes": ITEM_BYTES,
                "drains_closed": len(closed),
                "peak_lane_items": max((rec.sizes_max for rec in window),
                                       default=0),
            },
        }

    def finish(self) -> None:
        """Run the drain the window cut to its end, untimed."""
        carry, drain_rounds = self._carry, self._drain_rounds
        while True:
            carry, r = self._dispatch(carry)
            drain_rounds += r
            if r < self.fused or drain_rounds >= self.max_rounds:
                break
        self._close(carry, drain_rounds)

    def attempted(self) -> int:
        return len(self.drains)

    def kernel_rows(self) -> Dict[int, str]:
        """The queue kernels' kinds by the rows of their output: a pop
        returns the slots, a push or splice the ring, a window
        ``max_steal`` rows."""
        return {self.slots: "pop", self.ring: "ring_write",
                int(self.policy["max_steal"]): "window"}

    def checks(self) -> Tuple[List[Check], int]:
        ref = pareto.reference(self.n_nodes, self.fanout, *self.law,
                               self.salt)
        lost = sum(abs(d["nodes"] - ref["nodes"]) for d in self.drains)
        units = sum(abs(d["units"] - ref["units"]) for d in self.drains)
        bad_sums = [d["id_sum"] != ref["id_sum"]
                    or d["hash_sum"] != ref["hash_sum"] for d in self.drains]
        left = [sum(d["final_sizes"]) + d["in_flight"] for d in self.drains]
        failed = sum(d["nodes"] != ref["nodes"] or d["units"] != ref["units"]
                     or bad or n_left != 0
                     for d, bad, n_left in zip(self.drains, bad_sums, left))
        return ([("nodes_lost_or_repeated", lost, 0),
                 ("units_wrong", units, 0),
                 ("drains_with_wrong_checksum", sum(bad_sums), 0),
                 ("items_left_in_queues_or_in_flight", sum(left), 0)],
                failed)
