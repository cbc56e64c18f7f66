"""Containers and drivers for per-worker queues across a mesh axis.

``ShardedQueues`` stacks W independent :class:`QueueState`s along a leading
axis.  Two execution modes share the exact same superstep code:

* ``run_vmapped`` — ``jax.vmap(..., axis_name=...)`` over the stacked axis:
  runs on a single device; used by unit/property tests and the CPU solver.
* ``run_sharded`` — ``shard_map`` over a real mesh axis: each device owns its
  lane; used by the production launcher and the dry-run.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ops as q_ops
from repro.core.policy import StealPolicy
from repro.core import master as master_ops

Pytree = Any

__all__ = ["make_sharded_queues", "vmapped_superstep", "sharded_superstep"]


def make_sharded_queues(n_workers: int, capacity: int, item_spec: Pytree,
                        *, sharding: NamedSharding | None = None
                        ) -> q_ops.QueueState:
    """A stacked pytree of W empty queues (leading axis = worker).

    ``sharding`` optionally places every leaf with a
    :class:`~jax.sharding.NamedSharding` over the leading worker axis
    (one ring shard per device along the mesh's worker axes) — what the
    mesh executor passes so each device OWNS its lane's ring from the
    first byte; omitted, the stack lives wherever jax defaults (single
    device), which is what the vmap-lane executor wants."""
    buf = jax.tree_util.tree_map(
        lambda s: jnp.zeros((n_workers, capacity) + tuple(s.shape), dtype=s.dtype),
        item_spec,
    )
    qs = q_ops.QueueState(
        buf=buf,
        lo=jnp.zeros((n_workers,), jnp.int32),
        size=jnp.zeros((n_workers,), jnp.int32),
    )
    if sharding is not None:
        qs = jax.device_put(qs, sharding)
    return qs


def vmapped_superstep(policy: StealPolicy, axis_name: str = "workers",
                      ops: q_ops.BulkOps | None = None) -> Callable:
    """Single-device driver: the superstep vmapped over the worker axis with
    collectives resolved through the vmap axis name.  ``ops`` optionally
    pins the :class:`~repro.core.ops.BulkOps` backend (otherwise it is
    resolved from ``policy.backend`` at trace time)."""

    def step(qs: q_ops.QueueState):
        return jax.vmap(
            functools.partial(master_ops.superstep, policy=policy,
                              axis_name=axis_name, ops=ops),
            axis_name=axis_name,
        )(qs)

    return jax.jit(step)


def sharded_superstep(
    mesh: Mesh,
    policy: StealPolicy,
    worker_axis: str = "data",
    pod_axis: str | None = None,
    ops: q_ops.BulkOps | None = None,
) -> Callable:
    """Production driver: shard_map over the mesh's worker axis (one queue
    per device along that axis); optionally hierarchical over a pod axis.

    Returns ``(queues, stats)`` with the FULL
    :class:`~repro.core.master.RebalanceStats` (replicated leaves
    returned once, scalar counters as shape ``(1,)`` arrays), exactly
    like the vmapped driver — not just ``sizes_after``.  In flat mode
    every field is replicated so the single copy is exact; in
    hierarchical mode the copy is the lane-(pod 0, worker 0) view (pod
    0's intra-pod share plus the xpod share, which is what the
    representatives see — the same element the executor's exact
    aggregation reads first).  ``ops``
    optionally pins the :class:`~repro.core.ops.BulkOps` backend shared
    by both levels; when omitted it is resolved from ``policy.backend``
    and the queue geometry at trace time, so a pinned
    ``StealPolicy(backend=...)`` selects the same implementation here as
    everywhere else.
    """
    axes = (pod_axis, worker_axis) if pod_axis else (worker_axis,)
    spec = P(axes)

    if pod_axis is None:
        def inner(qs):
            q = jax.tree_util.tree_map(lambda x: x[0], qs)  # strip lane dim
            q, stats = master_ops.superstep(q, policy,
                                            axis_name=worker_axis, ops=ops)
            return (
                jax.tree_util.tree_map(lambda x: x[None], q),
                jax.tree_util.tree_map(jnp.atleast_1d, stats),
            )
    else:
        def inner(qs):
            q = jax.tree_util.tree_map(lambda x: x[0], qs)
            q, stats = master_ops.hierarchical_superstep(
                q, policy, worker_axis=worker_axis, pod_axis=pod_axis,
                ops=ops
            )
            return (
                jax.tree_util.tree_map(lambda x: x[None], q),
                jax.tree_util.tree_map(jnp.atleast_1d, stats),
            )

    stats_spec = master_ops.RebalanceStats(
        *([P(None)] * len(master_ops.RebalanceStats._fields)))
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(q_ops.QueueState(buf=spec, lo=spec, size=spec),),
        out_specs=(
            q_ops.QueueState(buf=spec, lo=spec, size=spec),
            stats_spec,
        ),
        check_vma=False,
    )
    return jax.jit(fn)
