"""Master-worker parallel DD branch-and-bound on the lock-free bulk queues.

This is the paper's target system end-to-end: W workers each own a
private subproblem queue; exploring a subproblem generates children in
BULK (one vectorized push); the virtual master (core.master.superstep)
observes queue sizes and bulk-steals proportionally from busy workers to
feed drained ones — the single-stealer, watermark-gated policy of §II.B.

The solver runs on :class:`repro.runtime.StealRuntime` — the unified
executor — so its steal hot path is the same backend-routed, adaptively
tuned path the benchmarks and the serving scheduler exercise.  The
per-worker body (vmapped over the worker axis; the same code shard_maps
onto a mesh axis) drives the runtime's resolved
:class:`~repro.core.ops.BulkOps` backend for its owner-side ops:

  1. ops.pop_bulk(E)       — owner-side bulk pop
  2. explore_batch         — restricted/relaxed DD bounds + exact frontier
  3. pmax incumbent        — global bound (the master's bookkeeping)
  4. prune + compact       — children of dominated nodes are dropped
  5. ops.push(children)    — owner-side bulk push

and the runtime appends 6. master.superstep (proportional bulk-steal
rebalancing with the adaptive proportion) and records telemetry.  By
default the solver advances ``fused_rounds`` supersteps per device
dispatch (``StealRuntime.run_fused``): explore, rebalance and the
adaptive update are one on-device loop that early-exits at drain, so the
hot loop never leaves the device between supersteps.

The incumbent is monotone and every subproblem is either solved exactly,
pruned, or partitioned by its children, so the parallel solver returns
the same optimum as the sequential oracle (tests assert this).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.dd.bnb import Subproblem, explore_batch
from repro.core.dd.diagram import NEG
from repro.core.dd.knapsack import Knapsack
from repro.core.ops import BulkOps, QueueState
from repro.core.policy import StealPolicy
from repro.obs import spans

__all__ = ["parallel_solve"]

AXIS = "workers"


def _item_spec():
    z = jnp.zeros((), jnp.int32)
    return {"layer": z, "state": z, "value": z}


def _make_worker_body(weights, profits, ops: BulkOps, *, explore_width: int,
                      batch: int, n_vars: int):
    """One worker's slice of the solver superstep (runs under vmap with
    the runtime's axis name in scope).  ``ops`` is the runtime's resolved
    BulkOps backend, so the owner-side bulk pop and push run the same
    routing (Pallas ring-slice / ring-scatter when resolved) as the
    master's steal."""

    def body(q: QueueState, carry):
        # 1. bulk pop up to `batch` subproblems
        q, items, n_popped = ops.pop_bulk(q, batch, jnp.int32(batch))
        valid = jnp.arange(batch, dtype=jnp.int32) < n_popped
        subs = Subproblem(layer=items["layer"], state=items["state"],
                          value=items["value"])

        # 2. explore
        out = explore_batch(subs, valid, weights, profits,
                            width=explore_width, n_vars=n_vars)

        # 3. global incumbent via the master's bookkeeping (all-reduce max)
        local_best = jnp.maximum(carry["incumbent"], jnp.max(out["primal"]))
        incumbent = lax.pmax(local_best, AXIS)

        # 4. prune: a subproblem's children survive iff dual > incumbent
        keep = (out["dual"] > incumbent)[:, None]
        ch = out["children"]
        live = keep & (ch.layer >= 0)                  # (batch, width)
        flat = {
            "layer": ch.layer.reshape(-1),
            "state": ch.state.reshape(-1),
            "value": ch.value.reshape(-1),
        }
        flive = live.reshape(-1)
        # compact live children to the front (single sort — bulk, no
        # per-node op)
        order = jnp.argsort(~flive, stable=True)
        flat = jax.tree_util.tree_map(lambda x: x[order], flat)
        n_children = jnp.sum(flive.astype(jnp.int32))

        # 5. bulk push (step 6, the rebalancing superstep, is appended by
        # the runtime)
        q, _ = ops.push(q, flat, n_children)
        return q, {"incumbent": incumbent,
                   "explored": carry["explored"] + n_popped}

    return body


def parallel_solve(inst: Knapsack, *, n_workers: int = 8,
                   explore_width: int = 16, batch: int = 8,
                   capacity: int = 4096, policy: StealPolicy | None = None,
                   max_supersteps: int = 10_000, adaptive: bool = True,
                   backend: str | BulkOps | None = None,
                   fused_rounds: int = 8,
                   execution: str = "vmap") -> Tuple[int, dict]:
    """Solve on W executor lanes — vmapped on one device by default, or
    one lane per device of a worker mesh with ``execution="mesh"`` (the
    solver body is mode-agnostic; both modes come from
    :func:`repro.distributed.launch_runtime` and run the identical
    fused round loop).

    ``backend`` optionally overrides the :class:`~repro.core.ops.BulkOps`
    routing for every queue op (master steal/splice AND the worker
    body's bulk pop/push); when omitted, ``policy.backend`` (default
    ``"auto"``) decides, resolved from the geometry at runtime
    construction.  ``fused_rounds > 1`` advances up to that many
    supersteps per device dispatch (``StealRuntime.run_fused`` — worker
    explore, rebalance and the adaptive proportion update all in one
    on-device loop, early-exiting at drain).

    Returns (optimum, stats); ``stats["telemetry"]`` carries the
    runtime's per-round rebalancing summary.
    """
    from repro.distributed.launch import launch_runtime

    with spans.span("solve:call"):
        spans.count("solve.calls")
        with spans.span("solve:build"):
            policy = policy or StealPolicy(proportion=0.5, high_watermark=4,
                                           low_watermark=0,
                                           max_steal=min(capacity, 1024))
            w = jnp.asarray(inst.weights, jnp.int32)
            p = jnp.asarray(inst.profits, jnp.int32)

            runtime = launch_runtime(n_workers, capacity, _item_spec(),
                                     execution=execution, policy=policy,
                                     adaptive=adaptive, backend=backend,
                                     max_pop=batch, axis_name=AXIS)
            # seed: root subproblem on worker 0
            runtime.push(0, {"layer": jnp.zeros((1,), jnp.int32),
                             "state": jnp.full((1,), inst.capacity,
                                               jnp.int32),
                             "value": jnp.zeros((1,), jnp.int32)}, 1)

            body = _make_worker_body(w, p, runtime.ops,
                                     explore_width=explore_width,
                                     batch=batch, n_vars=inst.n)
            carry = {"incumbent": jnp.full((n_workers,), NEG, jnp.int32),
                     "explored": jnp.zeros((n_workers,), jnp.int32)}

        carry = runtime.run(body, carry, max_rounds=max_supersteps,
                            fused=fused_rounds)

        with spans.span("solve:result"):
            optimum = int(carry["incumbent"][0])
            stats = {
                "supersteps": runtime.rounds_run,
                "explored": int(jnp.sum(carry["explored"])),
                "transferred": runtime.telemetry.total_transferred,
                "per_worker_explored": [int(x) for x in carry["explored"]],
                "telemetry": runtime.telemetry.summary(),
                "backend": runtime.ops.resolved,
                "execution": execution,
            }
    return optimum, stats
