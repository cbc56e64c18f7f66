"""``knapsack_solve``: a closed loop of ``parallel_solve`` calls.

One call per instance, over the traffic's fixed pool of instances in an
order drawn from the seed, each exactly as a user makes it, on the lanes
and in the execution mode of the cell's layout.  The window ends at the
end of the pass over the pool during which its time ran out, so every
run solves whole passes of the same instances (a traced window ends at
the solve during which its time ran out).
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Tuple

import numpy as np

from bench import knapsack
from bench.jobs import Check, CompileCounter, annotate


class SizeSpy:
    """Reads each lane's queue size at the start of every round from the
    per-round ``RebalanceStats`` that ``StealRuntime.run_fused`` returns
    (``sizes_after`` of one round is the next round's start), for a
    caller such as ``parallel_solve`` that keeps its runtime to itself.
    Installed only in traced runs; it returns what it wraps unchanged."""

    def __init__(self):
        self.lane_rounds = 0
        self.starved = 0
        self._start = None

    def begin_solve(self, lanes: int) -> None:
        # parallel_solve seeds its root on lane 0.
        self._start = np.zeros(lanes, np.int64)
        self._start[0] = 1

    def record(self, stats) -> None:
        after = np.asarray(stats.sizes_after)  # (rounds, lanes, lanes)
        for row in after[:, 0, :]:
            self.starved += int(np.sum(self._start == 0))
            self.lane_rounds += int(self._start.size)
            self._start = row

    @classmethod
    @contextlib.contextmanager
    def installed(cls, on: bool):
        if not on:
            yield None
            return
        from repro.runtime.executor import StealRuntime

        spy, orig = cls(), StealRuntime.run_fused

        def run_fused(self, *args, **kwargs):
            out = orig(self, *args, **kwargs)
            spy.record(out[1])
            return out

        StealRuntime.run_fused = run_fused
        try:
            yield spy
        finally:
            StealRuntime.run_fused = orig


class Driver:
    """Closed loop of ``parallel_solve`` calls over a fixed pool."""

    def __init__(self, config: dict, traffic: dict, layout: dict, seed: int):
        shape = config["instances"]
        self.solver = dict(config["solver"])
        self.solver["n_workers"] = int(layout["lanes"])
        self.solver["execution"] = layout["execution"]
        cls = traffic["class"]
        params = dict(n=int(shape["n"]), r=int(shape["R"]), h=int(shape["h"]),
                      big_h=int(shape["H"]))
        size = int(traffic["pool_size"])
        self.pool = knapsack.pool(cls, pool_seed=int(traffic["pool_seed"]),
                                  size=size, **params)
        self.warm = knapsack.instance(cls, seed=int(traffic["pool_seed"]),
                                      index=size, **params)
        self.order = knapsack.order(seed, size)
        self.solves: List[dict] = []
        self.compiles = CompileCounter()
        self.spy = None

    def _solve(self, inst: knapsack.Instance) -> Tuple[int, dict]:
        from repro.core.dd.knapsack import Knapsack
        from repro.core.dd.parallel import parallel_solve

        if self.spy is not None:
            self.spy.begin_solve(self.solver["n_workers"])
        with annotate("parallel_solve"):
            return parallel_solve(Knapsack(inst.weights, inst.profits,
                                           inst.capacity), **self.solver)

    def setup(self) -> None:
        self.compiles.__enter__()
        self._solve(self.warm)

    def window(self, seconds: float, clock: Callable[[], float],
               traced: bool = False) -> dict:
        c0 = self.compiles.count
        supersteps = 0
        with SizeSpy.installed(traced) as self.spy:
            t0 = clock()
            deadline = t0 + seconds
            with annotate("window"):
                done = False
                while not done:
                    for i in self.order:
                        ts = clock()
                        opt, stats = self._solve(self.pool[i])
                        t1 = clock()
                        self.solves.append({"index": i, "optimum": opt,
                                            "seconds": t1 - ts})
                        supersteps += int(stats["supersteps"])
                        # A traced window ends at a solve, which keeps
                        # the trace short; a timed one at a whole pass.
                        if traced and t1 >= deadline:
                            done = True
                            break
                    done = done or t1 >= deadline
        elapsed = t1 - t0
        n = len(self.solves)
        counters = {"solves": n, "rounds": supersteps,
                    "compiles": self.compiles.count - c0,
                    "solve_seconds": [s["seconds"] for s in self.solves]}
        if self.spy is not None:
            counters["lane_rounds"] = self.spy.lane_rounds
            counters["starved_lane_rounds"] = self.spy.starved
        self.spy = None
        return {"e2e": {"solve_s": elapsed / n}, "elapsed_s": elapsed,
                "counters": counters}

    def finish(self) -> None:
        self.compiles.__exit__(None, None, None)

    def attempted(self) -> int:
        return len(self.solves)

    def kernel_rows(self) -> Dict[int, str]:
        return {}

    def checks(self) -> Tuple[List[Check], int]:
        want = [knapsack.dp_optimum(inst) for inst in self.pool]
        gaps = [abs(s["optimum"] - want[s["index"]]) for s in self.solves]
        wrong = sum(g != 0 for g in gaps)
        return ([("solves_with_wrong_optimum", wrong, 0),
                 ("largest_optimum_gap", max(gaps, default=0), 0)], wrong)
