"""``uts_search``: a closed loop of whole UTS tree searches.

Back-to-back searches of one UTS binomial tree on one runtime, through
the program's own search (``repro.core.uts.UTSSearch``: the lanes from
``launch_runtime``, the worker body that spawns children by SHA-1 on the
device, the root's children seeded on lane ``seed mod lanes``),
dispatched as ``StealRuntime.run`` dispatches: ``run_fused(k,
until_drained=True)`` until a block comes back short.  Nodes are counted
at every dispatch boundary, so a search cut by the window's end counts
as far as it got.

Every search is compared with the plain reference (``bench/uts.py``, a
``hashlib`` walk that imports nothing of the program): the tree's
published node, leaf and depth counts, and the reference's salted
checksum of the digests.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

from bench import uts
from bench.jobs import Check, annotate

ITEM_BYTES = 24  # five digest words and a height, int32 each


class Driver:
    """Closed loop of whole searches of one UTS binomial tree."""

    def __init__(self, config: dict, traffic: dict, layout: dict, seed: int):
        self.tree = (int(config["b0"]), float(config["q"]), int(config["m"]),
                     int(config["r"]))
        self.published = {k: int(config[k])
                          for k in ("nodes", "leaves", "depth")}
        self.batch = int(config["pop_batch"])
        self.ring = int(config["ring_slots"])
        self.fused = int(config["fused_rounds"])
        self.backend = config["backend"]
        self.policy = dict(config["policy"])
        if layout["execution"] != "vmap":
            raise ValueError(f"UTS searches run their lanes vmapped on one "
                             f"chip, not {layout['execution']!r}")
        self.lanes = int(layout["lanes"])
        self.salt = uts.salt_for(seed)
        self.root_lane = seed % self.lanes
        # A search that has not ended after this many rounds has lost its
        # way (a sound one takes about nodes / (lanes * batch) rounds).
        self.max_rounds = int(traffic["max_rounds_factor"]) * (
            self.published["nodes"] // (self.lanes * self.batch) + 1)
        self.searches: List[dict] = []
        self.search = None

    # -- building blocks ----------------------------------------------------

    def _dispatch(self, carry):
        with annotate("run_fused"):
            return self.search.dispatch(carry)

    def setup(self) -> None:
        import jax

        from repro.core.policy import StealPolicy
        from repro.core.uts import BinomialTree, UTSSearch

        self.search = UTSSearch(
            BinomialTree(*self.tree), self.lanes, ring_slots=self.ring,
            pop_batch=self.batch, policy=StealPolicy(**self.policy),
            backend=self.backend, fused_rounds=self.fused, salt=self.salt)
        # Warm up: the fused block on empty lanes (it exits before its
        # first round), twice so that both the fresh carry and a carry
        # the block returned have been seen, then the seeding.
        carry = self.search.zero_carry()
        for _ in range(2):
            carry, _ = self._dispatch(carry)
        jax.block_until_ready(carry)
        self.search.seed(self.root_lane)
        jax.block_until_ready(self.search.rt.queues)

    # -- the window ----------------------------------------------------------

    def _close(self, carry, rounds: int) -> None:
        out = self.search.totals(carry)
        out["rounds"] = rounds
        out["final_sizes"] = [int(x) for x in self.search.rt.sizes()]
        self.searches.append(out)

    def window(self, seconds: float, clock: Callable[[], float],
               traced: bool = False) -> dict:
        del traced  # the counts below come from the worker body's carry
        tele0 = len(self.search.rt.telemetry.rounds)
        carry = self.search.zero_carry()
        rounds, search_rounds = 0, 0
        closed0 = len(self.searches)
        t0 = clock()
        deadline = t0 + seconds
        with annotate("window"):
            while True:
                carry, r = self._dispatch(carry)
                rounds += r
                search_rounds += r
                if r < self.fused or search_rounds >= self.max_rounds:
                    self._close(carry, search_rounds)
                    carry, search_rounds = self.search.zero_carry(), 0
                    self.search.seed(self.root_lane)
                t1 = clock()
                if t1 >= deadline:
                    break
        self._carry, self._search_rounds = carry, search_rounds
        closed = self.searches[closed0:]
        parts = closed + [self.search.totals(carry)]
        nodes = sum(s["nodes"] for s in parts)
        # Items the lanes popped: every node but the roots; items pushed:
        # m children of every popped node that has them, and the root's
        # b0 children of each search seeded in the window.
        popped = nodes - len(parts)
        parents = popped - sum(s["leaves"] for s in parts)
        b0, _, m, _ = self.tree
        records = self.search.rt.telemetry.rounds[tele0:]
        elapsed = t1 - t0
        return {
            "e2e": {"nodes_per_s": nodes / elapsed},
            "elapsed_s": elapsed,
            "counters": {
                "rounds": rounds,
                "lane_rounds": rounds * self.lanes,
                "starved_lane_rounds": sum(s["starved"] for s in parts),
                "popped": popped,
                "pushed": m * parents + b0 * len(closed),
                "transferred": sum(rec.n_transferred for rec in records),
                "item_bytes": ITEM_BYTES,
                "searches_closed": len(closed),
                "peak_lane_items": max((rec.sizes_max for rec in records),
                                       default=0),
            },
        }

    def finish(self) -> None:
        """Run the search the window cut to its end, untimed."""
        carry, search_rounds = self._carry, self._search_rounds
        while True:
            carry, r = self._dispatch(carry)
            search_rounds += r
            if r < self.fused or search_rounds >= self.max_rounds:
                break
        self._close(carry, search_rounds)

    def attempted(self) -> int:
        return len(self.searches)

    def kernel_rows(self) -> Dict[int, str]:
        """The queue kernels' kinds by the rows of their output: a pop
        returns the batch, a push or splice the ring, a window
        ``max_steal`` rows."""
        return {self.batch: "pop", self.ring: "ring_write",
                int(self.policy["max_steal"]): "window"}

    def checks(self) -> Tuple[List[Check], int]:
        ref = uts.reference(*self.tree, self.salt)
        if any(ref[k] != v for k, v in self.published.items()):
            raise ValueError(f"the reference walk gives {ref}, not the "
                             f"published {self.published}: the "
                             f"configuration's tree is not the one published")
        pub = self.published
        lost = sum(abs(s["nodes"] - pub["nodes"]) for s in self.searches)
        bad_sum = [s["hash_sum"] != ref["hash_sum"] for s in self.searches]
        bad_shape = [s["leaves"] != pub["leaves"] or s["depth"] != pub["depth"]
                     for s in self.searches]
        left = [sum(s["final_sizes"]) for s in self.searches]
        failed = sum(s["nodes"] != pub["nodes"] or bs or bh or n_left != 0
                     for s, bs, bh, n_left in zip(self.searches, bad_sum,
                                                  bad_shape, left))
        return ([("nodes_lost_or_repeated", lost, 0),
                 ("searches_with_wrong_checksum", sum(bad_sum), 0),
                 ("searches_with_wrong_shape", sum(bad_shape), 0),
                 ("items_left_in_queues", sum(left), 0)], failed)
