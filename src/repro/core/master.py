"""The virtual master: SPMD bulk work-stealing rebalancing.

The paper's master thread is the *single stealer* for every worker queue and
decides when/from whom/to whom work moves (§II.B).  On a TPU mesh there is no
shared memory to steal through; the equivalent construction is:

  1. ``all_gather`` the per-worker queue sizes (4 bytes/worker — the master's
     "bookkeeping").
  2. Every device runs :func:`repro.core.policy.plan_transfers` on the
     identical size vector, producing the identical ``(victim -> thief, n)``
     plan — a **replicated virtual master**.  At most one steal per victim
     per round preserves the paper's single-stealer invariant, now at
     superstep granularity.
  3. The stolen blocks move in one collective **exchange** and each thief
     splices its block with one bulk push.  Two exchange implementations
     share the plan (``StealPolicy.exchange``):

     ``"compact"`` (default)
         Each lane contributes ONE raw ``(max_steal, ...)`` tail window
         to an ``all_gather``; the victim's detach is a pure cursor bump
         (no masked block is materialized) and the thief cuts its
         victim's segment straight out of the gathered stack and splices
         it — one fused ``kernels.queue_transfer.ring_transfer`` kernel
         on a kernel-routed backend.  Injected payload is
         **O(max_steal)** per lane per round, independent of W.  A
         replicated ``lax.cond`` on the plan skips the window build, the
         collective and the splice entirely on rounds that move nothing
         (the plan is identical on all lanes, so every device takes the
         same branch).
     ``"dense"``
         The original construction: a ``(W, max_steal, ...)`` outbox per
         lane (only the thief's row populated) moved by ``all_to_all``,
         inbox collapsed by summation.  Injected payload is
         **O(W * max_steal)** per lane per round — kept as the exchange
         oracle the compact path is property-tested against, and as the
         baseline column of the Fig. 10 scaling benchmark.

Because the whole round is one deterministic collective schedule, the
paper's consistency re-checks (drain detection) are provably unnecessary
here: owner pops and master steals can never interleave within a round.
That argument is tested (property tests assert no task is lost or
duplicated across arbitrary rounds, and that both exchanges produce
identical queues).

Scaling: the compact exchange keeps the per-round collective payload flat
in W (``RebalanceStats.bytes_moved`` reports it; ``benchmarks/
fig10_scaling.py`` sweeps W x max_steal x exchange), so the flat
superstep now scales to W >= 256 without the O(W * max_steal) payload
blow-up the dense exchange pays.  For multi-pod meshes
:func:`hierarchical_superstep` still composes the same plan within each
pod and then across pod representatives — that matches the paper's
planned MPI extension (single coordinator per machine group, §II.B) and
keeps DCN traffic at one block per pod.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ops as bulk_ops
from repro.core.ops import QueueState
from repro.core.policy import StealPolicy, plan_transfers

__all__ = ["RebalanceStats", "superstep", "hierarchical_superstep",
           "gather_sizes"]

Pytree = Any


class RebalanceStats(NamedTuple):
    """Per-round observability (replicated values). NamedTuple => pytree.

    ``n_transferred`` / ``n_steals`` count the transfers planned at THIS
    level's axis (replicated across the lanes that computed the plan).
    Under :func:`hierarchical_superstep` they hold the intra-pod share
    only (distinct per pod, replicated within a pod) while
    ``n_transferred_xpod`` / ``n_steals_xpod`` hold the cross-pod share
    — nonzero only on each pod's lane-0 representative and replicated
    across pods there, so an exact global total is
    ``sum_over_pods(intra at lane 0) + xpod at any lane 0`` with no
    double counting (the flat superstep reports zeros for the xpod
    fields).

    ``bytes_moved`` is the payload this lane injected into the block
    exchange collective this round (items x item bytes; the 4-byte/lane
    size gathers and counts are excluded): ``W * max_steal * item_bytes``
    for the dense exchange — unconditionally, the outbox moves even when
    the plan is empty — vs ``max_steal * item_bytes`` for the compact
    exchange on rounds that transfer and 0 on rounds the fast path
    skips.  Unlike the transfer counters this field stays PER-LANE
    (saturated at INT32_MAX).  Under :func:`hierarchical_superstep`,
    ``bytes_moved`` holds the intra-pod injection and
    ``bytes_moved_xpod`` the pod-level one — which, exchange semantics
    being physical, is nonzero on every lane for the dense exchange
    (all lane groups pay the pod-level outbox) but only on transferring
    representatives for the compact one; the executor reports the
    busiest lane's total (max intra + xpod).
    """

    sizes_before: jnp.ndarray
    sizes_after: jnp.ndarray
    n_transferred: jnp.ndarray
    n_steals: jnp.ndarray
    n_transferred_xpod: jnp.ndarray
    n_steals_xpod: jnp.ndarray
    bytes_moved: jnp.ndarray
    bytes_moved_xpod: jnp.ndarray


def gather_sizes(q: QueueState, *, worker_axis: str,
                 pod_axis: str | None = None) -> jnp.ndarray:
    """The master's bookkeeping as ONE flat vector: every lane's true
    queue size, gathered over the worker axis (and, when two-level, the
    pod axis), in lane order ``pod * pod_size + worker`` — the same
    order the executors stack lanes in.  4 bytes per lane per level;
    replicated on every lane, so callers may feed it to the adaptive
    controller or a drain check and every device takes the same branch.
    Works identically under ``vmap(axis_name=...)`` and ``shard_map``.
    """
    with jax.named_scope("master.sizes"):
        sizes = lax.all_gather(q.size, worker_axis)  # (pod_size,) or (W,)
        if pod_axis is None:
            return sizes
        # (n_pods * pod_size,)
        return lax.all_gather(sizes, pod_axis).reshape(-1)


def _resolve_ops(policy: StealPolicy, q: QueueState) -> bulk_ops.BulkOps:
    """Resolve the BulkOps backend from ``policy.backend`` and the queue
    geometry — at trace time, once per compilation (this is where
    ``"auto"`` consults the kernel geometry predicates; the master's
    push is the thief splice, bounded by ``max_steal``)."""
    cap = jax.tree_util.tree_leaves(q.buf)[0].shape[0]
    return bulk_ops.make_ops(policy.backend, capacity=cap,
                             max_push=policy.max_steal,
                             max_steal=policy.max_steal)


def _item_nbytes(q: QueueState) -> int:
    """Static per-item payload bytes (trace-time): the ring leaves minus
    their leading capacity dimension, through the one shared
    ``ops.item_nbytes`` accounting."""
    return bulk_ops.item_nbytes(jax.tree_util.tree_map(
        lambda b: jax.ShapeDtypeStruct(b.shape[1:], b.dtype), q.buf))


def _payload_i32(nbytes: int) -> jnp.ndarray:
    """Static payload byte count as int32, saturated at INT32_MAX —
    ``bytes_moved`` is telemetry, and a >2 GiB/lane/round dense payload
    (huge items x large W) must not turn into a trace-time
    OverflowError."""
    return jnp.int32(min(int(nbytes), 2**31 - 1))


def _dense_exchange(q, ops, policy, axis_name, n_workers, me, idx, src, amt
                    ) -> Tuple[QueueState, jnp.ndarray]:
    """The O(W * max_steal)-payload exchange: per-lane outbox +
    ``all_to_all`` + summed inbox.  Kept as the oracle the compact path
    is tested against and as the Fig. 10 baseline column."""
    # Who steals from me, and how much?  (at most one thief per victim)
    steals_me = (src == me) & (amt > 0) & (idx != me)
    stolen_amt = jnp.sum(jnp.where(steals_me, amt, 0))
    thief_id = jnp.argmax(steals_me).astype(jnp.int32)  # 0 when none (amt==0)

    with jax.named_scope("master.exchange"):
        # Victim severs its tail block — single cursor bump linearizes.
        # With a kernel-routed backend the detach is the Pallas
        # ring-gather.
        q, block, n_out = ops.steal_exact(q, stolen_amt,
                                          max_steal=policy.max_steal)

        # Outbox: one row per peer, only the thief's row is populated.
        def _outbox(x):
            out = jnp.zeros((n_workers,) + x.shape, x.dtype)
            return out.at[thief_id].set(
                jnp.where(n_out > 0, x, jnp.zeros_like(x)))

        outbox = jax.tree_util.tree_map(_outbox, block)
        counts = jnp.zeros((n_workers,), jnp.int32).at[thief_id].set(n_out)

        # One bulk exchange: row j of the inbox is what peer j sent to me.
        inbox = jax.tree_util.tree_map(
            lambda x: lax.all_to_all(x, axis_name, split_axis=0,
                                     concat_axis=0),
            outbox,
        )
        counts_in = lax.all_to_all(counts, axis_name, split_axis=0,
                                   concat_axis=0)

    with jax.named_scope("master.splice"):
        # Thief splices: at most one row is non-empty, blocks are
        # pre-masked so a sum collapses the inbox without a gather.  With
        # a kernel-routed backend the splice is the Pallas ring-scatter
        # kernel.
        recv_n = jnp.sum(counts_in)
        recv = jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0), inbox)
        q, _ = ops.push(q, recv, recv_n)

    bytes_moved = _payload_i32(n_workers * policy.max_steal
                               * _item_nbytes(q))
    return q, bytes_moved


def _compact_exchange(q, ops, policy, axis_name, me, idx, sizes, src, amt
                      ) -> Tuple[QueueState, jnp.ndarray]:
    """The O(max_steal)-payload exchange: one raw window all_gather +
    thief-side fused cut-and-splice, with a replicated zero-transfer
    fast path."""
    max_steal = policy.max_steal
    cap = jax.tree_util.tree_leaves(q.buf)[0].shape[0]
    any_transfer = jnp.any(amt > 0)

    def move(q):
        with jax.named_scope("master.exchange"):
            # Victim side: how much the plan severs from me.  The detach
            # is the cursor bump alone — the collective carries my raw
            # window, so no masked intermediate block is ever
            # materialized.
            steals_me = (src == me) & (amt > 0) & (idx != me)
            stolen_amt = jnp.sum(jnp.where(steals_me, amt, 0))
            n_out = jnp.clip(stolen_amt, 0,
                             jnp.minimum(q.size, jnp.int32(max_steal)))
            window = ops.window(q, max_steal=max_steal)
            gathered = jax.tree_util.tree_map(
                lambda x: lax.all_gather(x, axis_name), window)
            q = QueueState(buf=q.buf, lo=(q.lo + n_out) % cap,
                           size=q.size - n_out)

        with jax.named_scope("master.splice"):
            # Thief side: my row of the replicated plan names my victim;
            # the count is re-derived from the same replicated inputs the
            # victim clamped against (sizes gathered BEFORE any cursor
            # moved), so victim and thief agree exactly.
            my_src = src[me]
            my_amt = amt[me]
            is_thief = (my_amt > 0) & (my_src != me)
            recv_n = jnp.where(
                is_thief,
                jnp.clip(my_amt, 0,
                         jnp.minimum(sizes[my_src], jnp.int32(max_steal))),
                0,
            )
            q, _ = ops.transfer(q, gathered, my_src, recv_n,
                                max_steal=max_steal)
        return q

    # Replicated fast path: the plan is identical on every lane, so all
    # devices take the same branch and rounds that move nothing skip the
    # window build, the collective and the splice entirely.
    q = lax.cond(any_transfer, move, lambda q: q, q)
    bytes_moved = jnp.where(any_transfer,
                            _payload_i32(max_steal * _item_nbytes(q)),
                            jnp.int32(0))
    return q, bytes_moved


def superstep(
    q: QueueState,
    policy: StealPolicy,
    *,
    axis_name: str,
    ops: bulk_ops.BulkOps | None = None,
    exchange: str | None = None,
    plan: jnp.ndarray | None = None,
) -> Tuple[QueueState, RebalanceStats]:
    """One rebalancing round.  Must run inside ``shard_map`` (or
    ``vmap(axis_name=...)`` for host-side testing) over ``axis_name`` where
    each lane owns one :class:`QueueState`.

    ``ops`` is the :class:`~repro.core.ops.BulkOps` backend serving the
    victim-side detach and the thief-side splice; when omitted it is
    resolved from ``policy.backend`` and the queue geometry ONCE at trace
    time (``"auto"`` consults the kernel geometry predicates here, never
    per call).  ``exchange`` overrides ``policy.exchange``
    (``"compact"`` / ``"dense"`` — see the module docstring).

    ``plan`` optionally substitutes the replicated transfer plan (int32
    ``(W, 2)``, the :func:`~repro.core.policy.plan_transfers` layout) for
    the one computed here.  The caller must have derived it from the SAME
    replicated inputs every lane sees (the gathered size vector before
    any cursor moved), so victim- and thief-side clamps still agree —
    this is how the resilience layer routes recovery steals (a dead
    lane's ring at proportion 1.0) through the existing exchange without
    new collectives or kernels.
    """
    if ops is None:
        ops = _resolve_ops(policy, q)
    if exchange is None:
        exchange = policy.exchange
    # psum of a literal folds to the static axis size (jax<0.5 has no
    # lax.axis_size).
    n_workers = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    idx = jnp.arange(n_workers, dtype=jnp.int32)

    # (1) master bookkeeping: gather sizes.
    with jax.named_scope("master.sizes"):
        sizes = lax.all_gather(q.size, axis_name)  # (W,) same on all lanes

    # (2) replicated plan.
    if plan is None:
        with jax.named_scope("master.plan"):
            plan = plan_transfers(sizes, policy)  # (W, 2): (victim, n)
    src, amt = plan[:, 0], plan[:, 1]

    # (3) the block exchange.
    if exchange == "dense":
        q, bytes_moved = _dense_exchange(q, ops, policy, axis_name,
                                         n_workers, me, idx, src, amt)
    elif exchange == "compact":
        q, bytes_moved = _compact_exchange(q, ops, policy, axis_name,
                                           me, idx, sizes, src, amt)
    else:
        raise ValueError(
            f"unknown exchange {exchange!r}; expected 'compact' or 'dense'")

    with jax.named_scope("master.sizes"):
        sizes_after = lax.all_gather(q.size, axis_name)
    if bulk_ops._env_check():
        # Sanitizer on (REPRO_CHECK=1, decided at trace time): assert in
        # trace that this level's exchange conserved its gathered sizes.
        from repro.analysis import sanitize

        cap = jax.tree_util.tree_leaves(q.buf)[0].shape[0]
        sanitize.trace_check_superstep(sizes, sizes_after, capacity=cap)
    stats = RebalanceStats(
        sizes_before=sizes,
        sizes_after=sizes_after,
        n_transferred=jnp.sum(jnp.where(amt > 0, amt, 0)),
        n_steals=jnp.sum((amt > 0).astype(jnp.int32)),
        n_transferred_xpod=jnp.int32(0),
        n_steals_xpod=jnp.int32(0),
        bytes_moved=bytes_moved,
        bytes_moved_xpod=jnp.int32(0),
    )
    return q, stats


def hierarchical_superstep(
    q: QueueState,
    policy: StealPolicy,
    *,
    worker_axis: str,
    pod_axis: str,
    ops: bulk_ops.BulkOps | None = None,
) -> Tuple[QueueState, RebalanceStats]:
    """Two-level rebalancing for multi-pod meshes: first the flat superstep
    within each pod (cheap ICI), then one superstep across pods where each
    pod's lane-0 worker acts as the pod representative (DCN-scale traffic is
    one block per pod, not per worker).  ``ops`` as in :func:`superstep`
    (resolved once, shared by both levels; the exchange routing follows
    ``policy.exchange`` at both levels)."""
    if ops is None:
        ops = _resolve_ops(policy, q)
    q, stats = superstep(q, policy, axis_name=worker_axis, ops=ops)

    # Cross-pod: only lane 0 of each pod participates with its real size;
    # other lanes advertise "full enough not to be idle, small enough not
    # to be a victim" so the plan ignores them.
    me = lax.axis_index(worker_axis)
    sentinel = jnp.int32(policy.low_watermark + 1)
    eff_size = jnp.where(me == 0, q.size, sentinel)
    q_eff = QueueState(buf=q.buf, lo=q.lo, size=eff_size)
    q_eff, pod_stats = superstep(q_eff, policy, axis_name=pod_axis, ops=ops)
    # Restore true size accounting for what moved at pod level.
    delta = q_eff.size - eff_size
    q = QueueState(buf=q_eff.buf, lo=q_eff.lo, size=q.size + delta)

    # Exact per-level accounting: the intra-pod share stays in
    # n_transferred/n_steals; the pod-level plan's counts go in the xpod
    # fields.  Lanes l > 0 gathered sentinel sizes at pod level, so their
    # pod_stats are zero — the xpod fields are nonzero only on lane-0
    # representatives, where they are replicated across pods.
    stats = stats._replace(
        n_transferred_xpod=pod_stats.n_transferred,
        n_steals_xpod=pod_stats.n_steals,
        bytes_moved_xpod=pod_stats.bytes_moved,
        sizes_after=pod_stats.sizes_after,
    )
    return q, stats
