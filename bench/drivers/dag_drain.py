"""``dag_drain``: a closed loop of Fig. 9 DAG drains.

Back-to-back drains on one ``StealRuntime`` (vmapped lanes on one chip,
or one lane per chip through ``launch_runtime(execution="mesh")``),
dispatched as ``StealRuntime.run`` dispatches them: ``run_fused(k,
until_drained=True)`` until a block comes back short.  Nodes are counted
at every dispatch boundary, so a drain cut by the window's end counts as
far as it got.

The worker body is the one ``benchmarks/fig9_dag._device_body`` runs
(pop a bulk, compute the children, compact them, push them back), copied
here so that the yardstick does not move when the program does.  It
also keeps, per lane, the counts the comparison and the per-layer
metrics read: nodes explored, two checksums of the explored ids
(``bench/dag.py`` computes the same sums by a plain walk), and the
rounds the lane began with an empty queue.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

from bench import dag
from bench.jobs import Check, annotate


def make_body(n_nodes: int, batch: int, fanout: int, salt: int, ops):
    """The per-lane worker body ``(q, carry) -> (q, carry)``."""
    import jax.numpy as jnp

    fan = jnp.int32(fanout)
    offsets = jnp.arange(fanout, dtype=jnp.int32)[None, :]
    usalt = jnp.uint32(salt)
    mul = jnp.uint32(dag.HASH_MUL)

    def body(q, carry):
        starved = (q.size == 0).astype(jnp.int32)
        q, nodes, n_popped = ops.pop_bulk(q, batch, jnp.int32(batch))
        valid = jnp.arange(batch, dtype=jnp.int32) < n_popped
        kids = nodes[:, None] * fan + 1 + offsets
        live = valid[:, None] & (kids < n_nodes)
        flat, flive = kids.reshape(-1), live.reshape(-1)
        order = jnp.argsort(~flive, stable=True)  # compact live to front
        flat = jnp.where(flive[order], flat[order], 0)
        q, _ = ops.push(q, flat, jnp.sum(flive.astype(jnp.int32)))
        ids = nodes.astype(jnp.uint32)
        h = (ids ^ usalt) * mul
        h = h ^ (h >> 15)
        zero = jnp.uint32(0)
        return q, {
            "explored": carry["explored"] + jnp.sum(valid.astype(jnp.int32)),
            "id_sum": carry["id_sum"] + jnp.sum(jnp.where(valid, ids, zero)),
            "hash_sum": carry["hash_sum"] + jnp.sum(jnp.where(valid, h, zero)),
            "starved": carry["starved"] + starved,
        }

    return body


def zero_carry(lanes: int):
    import jax.numpy as jnp

    return {"explored": jnp.zeros((lanes,), jnp.int32),
            "id_sum": jnp.zeros((lanes,), jnp.uint32),
            "hash_sum": jnp.zeros((lanes,), jnp.uint32),
            "starved": jnp.zeros((lanes,), jnp.int32)}


class Driver:
    """Closed loop of Fig. 9 DAG drains."""

    def __init__(self, config: dict, traffic: dict, layout: dict, seed: int):
        self.n_nodes = int(config["nodes"])
        self.fanout = int(config["fanout"])
        self.batch = int(config["pop_batch"])
        self.ring = int(config["ring_slots"])
        self.fused = int(config["fused_rounds"])
        self.backend = config["backend"]
        self.policy = dict(config["policy"])
        self.lanes = int(layout["lanes"])
        self.execution = layout["execution"]
        self.salt = dag.salt_for(seed)
        self.root_lane = seed % self.lanes
        # A drain that has not ended after this many rounds has lost its
        # way (a sound drain takes about n / (lanes * batch) rounds).
        self.max_rounds = int(traffic["max_rounds_factor"]) * (
            self.n_nodes // (self.lanes * self.batch) + 1)
        self.drains: List[dict] = []
        self.rt = None

    # -- building blocks ----------------------------------------------------

    def _seed(self) -> None:
        import jax.numpy as jnp

        self.rt.push(self.root_lane, jnp.zeros((1,), jnp.int32), 1)

    def _dispatch(self, carry):
        with annotate("run_fused"):
            carry, _stats, rounds = self.rt.run_fused(
                self.fused, self.body, carry, until_drained=True)
        return carry, rounds

    def setup(self) -> None:
        import jax

        from repro.core.policy import StealPolicy
        from repro.distributed import launch_runtime

        self.rt = launch_runtime(
            self.lanes, self.ring, jax.ShapeDtypeStruct((), "int32"),
            execution=self.execution, backend=self.backend,
            max_pop=self.batch, policy=StealPolicy(**self.policy))
        self.body = make_body(self.n_nodes, self.batch, self.fanout,
                              self.salt, self.rt.ops)
        # Warm up: the fused block on empty lanes (it exits before its
        # first round), twice so that both the fresh carry and a carry
        # the block returned have been seen, then the seeding push.
        carry = zero_carry(self.lanes)
        for _ in range(2):
            carry, _ = self._dispatch(carry)
        jax.block_until_ready(carry)
        self._seed()
        jax.block_until_ready(self.rt.queues)

    # -- the window ----------------------------------------------------------

    def _close_drain(self, carry, rounds: int) -> None:
        out = dag.totals(carry)
        out["rounds"] = rounds
        out["final_sizes"] = [int(x) for x in self.rt.sizes()]
        self.drains.append(out)

    def window(self, seconds: float, clock: Callable[[], float],
               traced: bool = False) -> dict:
        del traced  # the counts below come from the worker body's carry
        tele0 = len(self.rt.telemetry.rounds)
        carry = zero_carry(self.lanes)
        done_nodes, done_starved, rounds, drain_rounds = 0, 0, 0, 0
        closed0 = len(self.drains)
        t0 = clock()
        deadline = t0 + seconds
        with annotate("window"):
            while True:
                carry, r = self._dispatch(carry)
                rounds += r
                drain_rounds += r
                if r < self.fused or drain_rounds >= self.max_rounds:
                    self._close_drain(carry, drain_rounds)
                    done_nodes += self.drains[-1]["explored"]
                    done_starved += self.drains[-1]["starved"]
                    carry, drain_rounds = zero_carry(self.lanes), 0
                    self._seed()
                t1 = clock()
                if t1 >= deadline:
                    break
        self._carry, self._drain_rounds = carry, drain_rounds
        partial = dag.totals(carry)
        nodes = done_nodes + partial["explored"]
        closed = len(self.drains) - closed0
        # Every explored node but a root was pushed by its parent in the
        # worker body; each drain closed in the window seeded the next.
        roots = closed + (partial["explored"] > 0)
        transferred = sum(rec.n_transferred
                          for rec in self.rt.telemetry.rounds[tele0:])
        elapsed = t1 - t0
        return {
            "e2e": {"nodes_per_s": nodes / elapsed},
            "elapsed_s": elapsed,
            "counters": {
                "rounds": rounds,
                "lane_rounds": rounds * self.lanes,
                "starved_lane_rounds": done_starved + partial["starved"],
                "popped": nodes,
                "pushed": nodes - roots + closed,
                "transferred": transferred,
                "item_bytes": 4,
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
        self._close_drain(carry, drain_rounds)

    def attempted(self) -> int:
        return len(self.drains)

    def kernel_rows(self) -> Dict[int, str]:
        """The queue kernels' kinds by the rows of their output: a pop
        returns the batch, a push or splice the ring, a window
        ``max_steal`` rows."""
        return {self.batch: "pop", self.ring: "ring_write",
                int(self.policy["max_steal"]): "window"}

    def checks(self) -> Tuple[List[Check], int]:
        ref = dag.reference(self.n_nodes, self.fanout, self.salt)
        lost = sum(abs(d["explored"] - ref["explored"]) for d in self.drains)
        bad_sums = sum(d["id_sum"] != ref["id_sum"]
                       or d["hash_sum"] != ref["hash_sum"]
                       for d in self.drains)
        left = sum(sum(d["final_sizes"]) for d in self.drains)
        failed = sum(d["explored"] != ref["explored"]
                     or d["id_sum"] != ref["id_sum"]
                     or d["hash_sum"] != ref["hash_sum"]
                     or sum(d["final_sizes"]) != 0 for d in self.drains)
        return ([("nodes_lost_or_repeated", lost, 0),
                 ("drains_with_wrong_checksum", bad_sums, 0),
                 ("items_left_in_queues", left, 0)], failed)
