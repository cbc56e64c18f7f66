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

Solves reuse their programs.  An instance's data enters the fused block
as arguments: ``weights`` and ``profits`` ride in the carry, one row a
lane, and ``capacity`` only in the root the host pushes.  So the traced
program depends on the call's static geometry alone, and a process keeps
the worker body and its compiled programs of the last few geometries
(:data:`_PROGRAMS`, least recently used first out).  The key is
everything that shapes the trace: ``inst.n``, ``n_workers``,
``capacity``, ``explore_width``, ``batch``, ``fused_rounds``, the
policy, ``adaptive``, the resolved backend with the methods of its
class that the trace calls (a patched ``BulkOps`` method is a new key),
``execution`` and the mesh.  Each call still builds its own runtime:
empty queues, a fresh controller and telemetry.  Under the sanitizer
(``REPRO_CHECK``) every call traces its own program.
"""

from __future__ import annotations

import collections
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.dd.bnb import Subproblem, explore_batch
from repro.core.dd.diagram import NEG
from repro.core.dd.knapsack import Knapsack
from repro.core.ops import BulkOps, QueueState, make_ops
from repro.core.policy import StealPolicy
from repro.obs import spans

__all__ = ["parallel_solve"]

AXIS = "workers"

# The BulkOps methods the worker body and the superstep call while
# tracing; the program key holds what the backend's class binds them to.
_TRACED_OPS = ("push", "pop", "pop_bulk", "steal", "steal_exact", "window",
               "transfer")
# Program key -> (worker body, the runtimes' program table), oldest first.
_PROGRAMS: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_PROGRAMS_KEPT = 8


def _item_spec():
    z = jnp.zeros((), jnp.int32)
    return {"layer": z, "state": z, "value": z}


def _make_worker_body(ops: BulkOps, *, explore_width: int, batch: int,
                      n_vars: int):
    """One worker's slice of the solver superstep (runs under vmap with
    the runtime's axis name in scope).  ``ops`` is the runtime's resolved
    BulkOps backend, so the owner-side bulk pop and push run the same
    routing (Pallas ring-slice / ring-scatter when resolved) as the
    master's steal.  The instance's ``weights`` and ``profits`` come in
    the carry, so one body serves every instance of its geometry."""

    def body(q: QueueState, carry):
        # 1. bulk pop up to `batch` subproblems
        q, items, n_popped = ops.pop_bulk(q, batch, jnp.int32(batch))
        valid = jnp.arange(batch, dtype=jnp.int32) < n_popped
        subs = Subproblem(layer=items["layer"], state=items["state"],
                          value=items["value"])

        # 2. explore
        out = explore_batch(subs, valid, carry["weights"], carry["profits"],
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
        return q, {**carry, "incumbent": incumbent,
                   "explored": carry["explored"] + n_popped}

    return body


def _program_key(n_vars: int, ops: BulkOps, mesh, **static
                 ) -> Optional[tuple]:
    """What shapes the solver's traced program, or ``None`` where it may
    not be shared: under the sanitizer, whose traced checks report to
    the call that traced them, or with a backend whose state is not
    hashable."""
    from repro.analysis.sanitize import CheckedBulkOps

    if isinstance(ops, CheckedBulkOps):
        return None
    cls = type(ops)
    key = (n_vars, tuple(sorted(static.items())), mesh, cls,
           tuple(sorted(vars(ops).items())),
           tuple(getattr(cls, name) for name in _TRACED_OPS))
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _programs_for(key: Optional[tuple], make_body):
    """``(body, programs, reused)`` for a program key: the body and
    program table kept for it (made on a miss), and whether the table
    already holds a compiled program.  ``key=None``: a new body and no
    shared table."""
    if key is None:
        return make_body(), None, False
    entry = _PROGRAMS.get(key)
    if entry is None:
        entry = _PROGRAMS[key] = (make_body(), {})
        if len(_PROGRAMS) > _PROGRAMS_KEPT:
            _PROGRAMS.popitem(last=False)
    else:
        _PROGRAMS.move_to_end(key)
    body, programs = entry
    return body, programs, bool(programs)


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
    ``"auto"``) decides, resolved here from the geometry (and the
    ``REPRO_QUEUE_BACKEND`` override).  ``fused_rounds > 1`` advances up
    to that many supersteps per device dispatch
    (``StealRuntime.run_fused`` — worker explore, rebalance and the
    adaptive proportion update all in one on-device loop, early-exiting
    at drain).

    Reuse: a call whose ``inst.n``, ``n_workers``, ``capacity``,
    ``explore_width``, ``batch``, ``fused_rounds``, ``policy``,
    ``adaptive``, resolved backend (its class, routing and the class's
    queue-op methods), ``execution`` and mesh match one of the last few
    calls in this process runs that call's compiled programs: no
    tracing, lowering or loading.  What is per call: the runtime
    (queues, controller, telemetry, round count), and the instance,
    whose ``weights`` and ``profits`` enter the programs as arguments
    and whose ``capacity`` enters in the root pushed from the host.
    Such a hit counts ``solve.programs_reused``; a miss traces, under
    ``steal:compile``.

    Returns (optimum, stats); ``stats["telemetry"]`` carries the
    runtime's per-round rebalancing summary.
    """
    from repro.distributed.launch import launch_runtime
    from repro.launch.mesh import make_worker_mesh

    with spans.span("solve:call"):
        spans.count("solve.calls")
        with spans.span("solve:build"):
            policy = policy or StealPolicy(proportion=0.5, high_watermark=4,
                                           low_watermark=0,
                                           max_steal=min(capacity, 1024))
            # The runtime's own resolution, done here so that the
            # program key holds the backend the runtime will run.
            ops = make_ops(
                policy.backend if backend is None else backend,
                capacity=capacity, max_push=policy.max_steal, max_pop=batch,
                max_steal=policy.max_steal)
            mesh = (make_worker_mesh(n_workers, axis_name=AXIS)
                    if execution == "mesh" else None)
            key = _program_key(inst.n, ops, mesh, execution=execution,
                               n_workers=n_workers, capacity=capacity,
                               explore_width=explore_width, batch=batch,
                               fused_rounds=fused_rounds, policy=policy,
                               adaptive=adaptive)
            body, programs, reused = _programs_for(
                key, lambda: _make_worker_body(
                    ops, explore_width=explore_width, batch=batch,
                    n_vars=inst.n))
            if reused:
                spans.count("solve.programs_reused")

            runtime = launch_runtime(n_workers, capacity, _item_spec(),
                                     execution=execution, mesh=mesh,
                                     policy=policy, adaptive=adaptive,
                                     backend=ops, max_pop=batch,
                                     axis_name=AXIS, programs=programs)
            # seed: root subproblem on worker 0
            runtime.push(0, {"layer": jnp.zeros((1,), jnp.int32),
                             "state": jnp.full((1,), inst.capacity,
                                               jnp.int32),
                             "value": jnp.zeros((1,), jnp.int32)}, 1)

            # The instance, one row a lane: arguments of the program.
            rows = np.broadcast_to(
                np.asarray([inst.weights, inst.profits], np.int32)[:, None],
                (2, n_workers, inst.n))
            carry = {"incumbent": jnp.full((n_workers,), NEG, jnp.int32),
                     "explored": jnp.zeros((n_workers,), jnp.int32),
                     "weights": jnp.asarray(rows[0]),
                     "profits": jnp.asarray(rows[1])}

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
