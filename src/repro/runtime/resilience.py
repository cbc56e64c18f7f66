"""Fault injection and recovery for the steal executors.

Production fleets lose and regain workers mid-run; the paper's own
mechanism is the recovery primitive — a dead worker is just a victim
stolen at proportion 1.0, and the multiplicity tolerance already
licensed for the relaxed backend bounds the duplication a crash between
an exchange and its splice can produce (DESIGN.md §8).  This module
supplies the machinery around that observation:

* :class:`FaultPlan` — a deterministic, seedable schedule of injected
  failures (kill lane w at round r, drop one round's exchange, delay a
  lane's worker body by k rounds).  The plan compiles to small
  replicated int32 arrays that ride into the jitted round as traced
  inputs, so the identical plan replays bit-identically under
  ``jax.vmap`` lanes and under ``shard_map`` meshes — and the host can
  mutate the schedule between dispatches (planned eviction, re-admission
  on grow) without recompiling.
* :func:`make_resilient_lane` — the fault-aware round body
  :func:`repro.runtime.executor.make_lane_step` delegates to.  Per
  round: the worker body's effects are discarded for dead/delayed lanes
  (the body still executes on every lane, so worker collectives stay
  collective), the normal rebalancing plan is computed with dead lanes
  masked out (neither idle-eligible nor victims), and then ONE extra
  recovery superstep runs whose replicated plan steals each dead lane's
  entire ring — ``min(size, max_steal, thief free space)`` per round,
  i.e. proportion 1.0 — into the least-loaded survivors, through the
  SAME exchange collectives and kernels as every other round (the
  zero-transfer fast path makes it free while nobody is dead).
* :func:`mask_sizes` — the size-vector mask the adaptive controller
  sees: dead lanes advertise the neither-idle-nor-busy sentinel, so the
  proportion servo never counts a corpse as an idle thief.

The fault context (``ctx``) threaded through the executors is either a
plain int32 round index (fault injection off — the compiled round is
byte-identical to the pre-resilience one) or a dict of the round index
plus the schedule arrays (fault injection on).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from repro.core import master as master_ops
from repro.core.policy import StealPolicy, plan_transfers

__all__ = [
    "NEVER",
    "FaultPlan",
    "FaultState",
    "ctx_round",
    "ctx_advance",
    "ctx_specs",
    "dead_mask",
    "mask_sizes",
    "masked_plan",
    "recovery_plan",
    "make_resilient_lane",
]

Pytree = Any

# "This lane is never killed": any round index compares < NEVER.
NEVER = np.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# Fault plans


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic schedule of injected failures.

    Attributes:
      kills: ``(lane, round)`` pairs — lane ``lane`` dies at the START of
        round ``round`` (it executes no worker body from that round on
        and is masked out of every plan; its ring is drained by recovery
        steals).  Round indices are GLOBAL (``StealRuntime.rounds_run``
        numbering), so a plan replays identically across ``round()`` /
        ``run_fused`` dispatch boundaries.
      delays: ``(lane, round, k)`` triples — lane ``lane`` skips its
        worker body for rounds ``[round, round + k)`` (a straggler: it
        still participates in exchanges, it just produces nothing).
      drops: round indices whose block exchange is dropped entirely (the
        plan is forced empty — both the normal and the recovery transfer
        move nothing that round; a lost collective, recovered next
        round).

    An empty ``FaultPlan()`` is meaningful: it arms the fault machinery
    (recovery supersteps, mutable kill schedule) without scheduling any
    failure — what planned eviction and the elastic serve master use.
    """

    kills: Tuple[Tuple[int, int], ...] = ()
    delays: Tuple[Tuple[int, int, int], ...] = ()
    drops: Tuple[int, ...] = ()

    @classmethod
    def random(cls, n_workers: int, *, seed: int, n_kills: int = 1,
               n_delays: int = 0, n_drops: int = 0,
               max_round: int = 16, max_delay: int = 4) -> "FaultPlan":
        """A seeded random plan: ``n_kills`` distinct lanes killed (never
        lane 0, so at least one survivor remains), ``n_delays`` straggler
        windows and ``n_drops`` dropped exchanges, all in rounds
        ``[1, max_round)``.  Same seed -> same plan -> same replay, in
        either execution mode."""
        rng = np.random.default_rng(seed)
        if n_kills >= n_workers:
            raise ValueError("cannot kill every lane")
        lanes = rng.choice(np.arange(1, n_workers), size=n_kills,
                           replace=False)
        kills = tuple((int(w), int(rng.integers(1, max_round)))
                      for w in lanes)
        delays = tuple((int(rng.integers(0, n_workers)),
                        int(rng.integers(1, max_round)),
                        int(rng.integers(1, max_delay + 1)))
                       for _ in range(n_delays))
        drops = tuple(int(rng.integers(1, max_round))
                      for _ in range(n_drops))
        return cls(kills=kills, delays=delays, drops=drops)

    def validate(self, n_workers: int) -> None:
        for w, r in self.kills:
            if not (0 <= w < n_workers):
                raise ValueError(f"kill lane {w} out of range [0, {n_workers})")
            if r < 0:
                raise ValueError(f"kill round {r} negative")
        for w, r, k in self.delays:
            if not (0 <= w < n_workers):
                raise ValueError(f"delay lane {w} out of range")
            if r < 0 or k < 1:
                raise ValueError(f"bad delay window ({r}, {k})")
        if len({w for w, _ in self.kills}) >= n_workers:
            raise ValueError("plan kills every lane; recovery needs a thief")


class FaultState:
    """Host-side, mutable compilation of a :class:`FaultPlan`.

    Owns the schedule arrays the jitted round consumes as traced inputs:
    ``kill_round[w]`` (NEVER = alive forever), one ``[delay_from,
    delay_until)`` straggler window per lane, and the padded
    ``drop_rounds`` vector.  Mutation (:meth:`kill` for planned eviction
    or detected death, :meth:`revive` for grow/re-admission) changes
    VALUES only — shapes are fixed at construction — so no dispatch ever
    recompiles."""

    def __init__(self, plan: FaultPlan, n_workers: int):
        plan.validate(n_workers)
        self.plan = plan
        self.n_workers = int(n_workers)
        self.kill_round = np.full((n_workers,), NEVER, np.int32)
        for w, r in plan.kills:
            self.kill_round[w] = min(self.kill_round[w], np.int32(r))
        self.delay_from = np.full((n_workers,), NEVER, np.int32)
        self.delay_until = np.full((n_workers,), NEVER, np.int32)
        for w, r, k in plan.delays:  # one window per lane; last wins
            self.delay_from[w] = np.int32(r)
            self.delay_until[w] = np.int32(r + k)
        drops = sorted(set(plan.drops))
        self.drop_rounds = np.asarray(drops or [-1], np.int32)

    # -- host mutation (no recompiles: values change, shapes don't) ---------

    def kill(self, lane: int, at_round: int) -> None:
        self.kill_round[lane] = np.int32(min(int(self.kill_round[lane]),
                                             int(at_round)))

    def revive(self, lane: int) -> None:
        self.kill_round[lane] = NEVER

    def dead_at(self, round_index: int) -> np.ndarray:
        """(W,) bool: which lanes are dead at ``round_index``."""
        return np.asarray(self.kill_round) <= np.int32(round_index)

    # -- the traced context --------------------------------------------------

    def ctx(self, round0: int) -> Dict[str, jnp.ndarray]:
        return {
            "round": jnp.int32(round0),
            "kill_round": jnp.asarray(self.kill_round),
            "delay_from": jnp.asarray(self.delay_from),
            "delay_until": jnp.asarray(self.delay_until),
            "drop_rounds": jnp.asarray(self.drop_rounds),
        }

    # -- snapshot/restore ----------------------------------------------------

    def state_dict(self) -> Dict[str, np.ndarray]:
        return {
            "kill_round": np.asarray(self.kill_round),
            "delay_from": np.asarray(self.delay_from),
            "delay_until": np.asarray(self.delay_until),
            "drop_rounds": np.asarray(self.drop_rounds),
        }

    def load_state(self, state: Dict[str, np.ndarray]) -> None:
        self.kill_round = np.asarray(state["kill_round"], np.int32).copy()
        self.delay_from = np.asarray(state["delay_from"], np.int32).copy()
        self.delay_until = np.asarray(state["delay_until"], np.int32).copy()
        self.drop_rounds = np.asarray(state["drop_rounds"], np.int32).copy()


# ---------------------------------------------------------------------------
# The traced fault context (scalar round index when injection is off)


def ctx_round(ctx) -> jnp.ndarray:
    """The current round index carried by a fault context."""
    return ctx["round"] if isinstance(ctx, dict) else ctx


def ctx_advance(ctx):
    """The context for the NEXT round (round index + 1, schedule shared)."""
    if isinstance(ctx, dict):
        return {**ctx, "round": ctx["round"] + 1}
    return ctx + 1


def ctx_specs(fault_active: bool):
    """The ``shard_map`` in/out spec for a fault context: everything in it
    is replicated (the round index and the schedule are the same on every
    lane — the virtual master's view)."""
    from jax.sharding import PartitionSpec as P

    if not fault_active:
        return P()
    return {"round": P(), "kill_round": P(), "delay_from": P(),
            "delay_until": P(), "drop_rounds": P()}


def dead_mask(ctx) -> jnp.ndarray:
    """(W,) bool, replicated: lanes dead at the context's round."""
    return ctx["kill_round"] <= ctx["round"]


def mask_sizes(sizes: jnp.ndarray, ctx, policy: StealPolicy) -> jnp.ndarray:
    """The size vector as the adaptive controller should see it: dead
    lanes advertise the hierarchical superstep's neither-idle-nor-busy
    sentinel (``low_watermark + 1``), so a drained corpse never counts as
    an idle thief and never inflates the steal proportion."""
    if not isinstance(ctx, dict):
        return sizes
    sentinel = jnp.int32(policy.low_watermark + 1)
    return jnp.where(dead_mask(ctx), sentinel, sizes)


# ---------------------------------------------------------------------------
# Replicated plans (pure jnp — every lane computes the identical answer)


def _noop_plan(n: int) -> jnp.ndarray:
    idx = jnp.arange(n, dtype=jnp.int32)
    return jnp.stack([idx, jnp.zeros((n,), jnp.int32)], axis=-1)


def masked_plan(sizes: jnp.ndarray, dead: jnp.ndarray,
                policy: StealPolicy) -> jnp.ndarray:
    """The normal rebalancing plan with dead lanes masked out: they are
    neither idle-eligible (work must not move INTO a corpse) nor victims
    (their whole ring belongs to the recovery plan, not a proportional
    steal).  Implemented as :func:`~repro.core.policy.plan_transfers`
    over a size vector where dead lanes advertise the sentinel — steal
    amounts are computed from victim rows, which are always alive, so
    they still read TRUE sizes and the exchange clamps agree."""
    sentinel = jnp.int32(policy.low_watermark + 1)
    return plan_transfers(jnp.where(dead, sentinel, sizes), policy)


def recovery_plan(sizes: jnp.ndarray, dead: jnp.ndarray, *,
                  max_steal: int, capacity: int,
                  thief_ok: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The dead-worker-as-victim plan: rank dead lanes that still hold
    work by size (fullest first) and surviving lanes by load (emptiest
    first), pair them, and steal ``min(size, max_steal, thief free
    space)`` — proportion 1.0, bounded per round by the exchange window,
    so a ring larger than ``max_steal`` drains over successive rounds.
    Same ``(W, 2)`` layout as :func:`~repro.core.policy.plan_transfers`;
    executed by the unmodified compact (or dense) exchange.

    ``thief_ok`` optionally restricts who may receive: the cross-pod
    recovery rows of the hierarchical lane pass the per-row liveness
    mask here, because a LIVE pod's lane in some row may itself be a
    dead lane — it must not be handed a dead pod's ring."""
    n = sizes.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    victim = dead & (sizes > 0)
    thief = ~dead if thief_ok is None else (thief_ok & ~dead)

    victim_order = jnp.argsort(jnp.where(victim, -sizes, jnp.int32(2**30)))
    thief_order = jnp.argsort(jnp.where(thief, sizes, jnp.int32(2**30)))
    n_pairs = jnp.minimum(jnp.sum(victim.astype(jnp.int32)),
                          jnp.sum(thief.astype(jnp.int32)))
    live = jnp.arange(n, dtype=jnp.int32) < n_pairs

    victim_of_pair = victim_order.astype(jnp.int32)
    thief_of_pair = thief_order.astype(jnp.int32)
    amt = jnp.minimum(sizes[victim_of_pair], jnp.int32(max_steal))
    # Never overflow the thief: proportion-1.0 steals ignore watermarks,
    # so the free-space clamp must be in the REPLICATED plan (both ends
    # derive their cut from it, so victim and thief stay in agreement).
    amt = jnp.minimum(amt, jnp.int32(capacity) - sizes[thief_of_pair])
    amt = jnp.where(live, jnp.maximum(amt, 0), 0)

    src = jnp.full((n,), idx, dtype=jnp.int32)
    src = src.at[thief_of_pair].set(
        jnp.where(live, victim_of_pair, thief_of_pair), mode="drop")
    amtv = jnp.zeros((n,), jnp.int32).at[thief_of_pair].set(amt, mode="drop")
    return jnp.stack([src, amtv], axis=-1)


# ---------------------------------------------------------------------------
# The fault-aware lane step


def _select(keep_old: jnp.ndarray, old: Pytree, new: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep_old, a, b), old, new)


def make_resilient_lane(policy: StealPolicy, ops, worker_fn, *,
                        axis_name: str, pod_axis: Optional[str] = None,
                        hierarchical: bool = False):
    """The fault-injecting round body for ONE lane:
    ``(q, carry, proportion, ctx) -> (q, carry, stats)`` — what
    :func:`repro.runtime.executor.make_lane_step` returns when the
    runtime was built with a :class:`FaultPlan`.

    Per round, in order: (1) the worker body runs on EVERY lane (worker
    collectives stay collective) but its effects are discarded on dead
    and delayed lanes; (2) the normal superstep executes the
    dead-masked plan; (3) one recovery superstep executes the
    dead-worker-as-victim plan (free via the zero-transfer fast path
    while nobody is dead).  Dropped-exchange rounds force both plans
    empty.  The merged stats keep the round's full transfer accounting
    (``sizes_before`` from before any exchange, ``sizes_after`` from
    after recovery, counters summed).

    With ``hierarchical=True`` (a 2-D ``(pod_axis, axis_name)`` lane
    grid) the round composes FOUR plans, all derived from the replicated
    schedule so every lane/mode agrees bit-for-bit:

    * the intra-pod normal plan with the pod's dead lanes masked;
    * the cross-pod normal plan over lane-0 representatives, where a pod
      whose representative is dead abstains (sentinel) until revival —
      its work still flows intra-pod, and its dead rep's ring drains
      intra-pod (a dead LANE is a pod-local event);
    * the intra-pod recovery plan (dead-fullest -> alive-emptiest within
      the pod);
    * the cross-pod recovery plan for ENTIRELY dead pods: each ring row
      ``w`` drains dead pods' lane-``w`` rings into the emptiest live
      pod's lane-``w``, with ``thief_ok`` excluding rows whose own lane
      is dead in an otherwise-live pod.

    Cross-pod recovery counts are folded onto lane-0 representatives
    (``psum`` over the worker axis), preserving the
    :func:`repro.runtime.telemetry.reduce_round_stats` accounting
    convention: xpod counters nonzero only at lane ``(p, 0)``.

    The plans are computed here, not in :func:`~repro.core.master.
    superstep`, so they run under this lane's own ``master.plan`` scope
    (the name the device trace attributes plan time to)."""
    if hierarchical and pod_axis is None:
        raise ValueError("hierarchical resilient lane needs a pod_axis")

    def flat_lane(q, carry, proportion, ctx):
        r = ctx_round(ctx)
        me = lax.axis_index(axis_name)
        i_am_dead = r >= ctx["kill_round"][me]
        i_am_delayed = (r >= ctx["delay_from"][me]) & (r < ctx["delay_until"][me])

        if worker_fn is not None:
            with jax.named_scope("worker_body"):
                q_new, carry_new = worker_fn(q, carry)
            skip = i_am_dead | i_am_delayed
            q = _select(skip, q, q_new)
            carry = _select(skip, carry, carry_new)

        pol = dataclasses.replace(policy, proportion=proportion)
        cap = jax.tree_util.tree_leaves(q.buf)[0].shape[0]
        dead = lax.all_gather(i_am_dead, axis_name)  # (W,) replicated
        drop = jnp.any(ctx["drop_rounds"] == r)

        # Normal rebalancing over the survivors.
        sizes = master_ops.gather_sizes(q, worker_axis=axis_name)
        with jax.named_scope("master.plan"):
            plan = masked_plan(sizes, dead, pol)
            plan = jnp.where(drop, _noop_plan(sizes.shape[0]), plan)
        q, stats = master_ops.superstep(q, pol, axis_name=axis_name,
                                        ops=ops, plan=plan)

        # Recovery: dead rings stolen at proportion 1.0 by the least-
        # loaded survivors, through the identical exchange.
        sizes2 = master_ops.gather_sizes(q, worker_axis=axis_name)
        with jax.named_scope("master.plan"):
            rplan = recovery_plan(sizes2, dead, max_steal=pol.max_steal,
                                  capacity=cap)
            rplan = jnp.where(drop, _noop_plan(sizes2.shape[0]), rplan)
        q, rstats = master_ops.superstep(q, pol, axis_name=axis_name,
                                         ops=ops, plan=rplan)

        stats = stats._replace(
            sizes_after=rstats.sizes_after,
            n_transferred=stats.n_transferred + rstats.n_transferred,
            n_steals=stats.n_steals + rstats.n_steals,
            bytes_moved=stats.bytes_moved + rstats.bytes_moved,
        )
        return q, carry, stats

    def hier_lane(q, carry, proportion, ctx):
        from repro.core.ops import QueueState

        r = ctx_round(ctx)
        # psum of a literal folds to the static axis size at trace time,
        # so these drive static reshapes/plan widths.
        pod_size = lax.psum(1, axis_name)
        n_pods = lax.psum(1, pod_axis)
        w_idx = lax.axis_index(axis_name)
        p_idx = lax.axis_index(pod_axis)
        me = p_idx * pod_size + w_idx  # flat lane order: pod-major

        # The schedule is replicated, so every liveness view derives
        # from ctx with no collectives: the flat mask, my pod's slice,
        # and the entirely-dead-pod vector.
        dead_flat = dead_mask(ctx)                     # (W,)
        dead2d = dead_flat.reshape(n_pods, pod_size)   # (n_pods, pod_size)
        dead_intra = dead2d[p_idx]                     # (pod_size,)
        pod_dead = jnp.all(dead2d, axis=1)             # (n_pods,)
        i_am_dead = dead_flat[me]
        i_am_delayed = (r >= ctx["delay_from"][me]) & (r < ctx["delay_until"][me])

        if worker_fn is not None:
            with jax.named_scope("worker_body"):
                q_new, carry_new = worker_fn(q, carry)
            skip = i_am_dead | i_am_delayed
            q = _select(skip, q, q_new)
            carry = _select(skip, carry, carry_new)

        pol = dataclasses.replace(policy, proportion=proportion)
        cap = jax.tree_util.tree_leaves(q.buf)[0].shape[0]
        drop = jnp.any(ctx["drop_rounds"] == r)

        # (1) Intra-pod normal superstep, the pod's dead lanes masked.
        sizes_pod = master_ops.gather_sizes(q, worker_axis=axis_name)
        with jax.named_scope("master.plan"):
            plan = masked_plan(sizes_pod, dead_intra, pol)
            plan = jnp.where(drop, _noop_plan(pod_size), plan)
        q, intra = master_ops.superstep(q, pol, axis_name=axis_name,
                                        ops=ops, plan=plan)

        # (2) Cross-pod normal superstep via lane-0 representatives —
        # the hierarchical_superstep sentinel trick, with a dead rep's
        # pod abstaining entirely (its work still flows intra-pod).
        sentinel = jnp.int32(pol.low_watermark + 1)
        rep_dead = dead2d[p_idx, 0]
        eff_size = jnp.where((w_idx == 0) & ~rep_dead, q.size, sentinel)
        q_eff = QueueState(buf=q.buf, lo=q.lo, size=eff_size)
        sizes_x = lax.all_gather(eff_size, pod_axis)   # (n_pods,) per row
        with jax.named_scope("master.plan"):
            pod_plan = plan_transfers(sizes_x, pol)
            pod_plan = jnp.where(drop, _noop_plan(n_pods), pod_plan)
        q_eff, pod_stats = master_ops.superstep(q_eff, pol,
                                                axis_name=pod_axis,
                                                ops=ops, plan=pod_plan)
        delta = q_eff.size - eff_size
        q = QueueState(buf=q_eff.buf, lo=q_eff.lo, size=q.size + delta)

        # (3) Intra-pod recovery: a dead LANE's ring drains into its
        # pod-mates (dead-fullest -> alive-emptiest, proportion 1.0).
        # No-op in an entirely dead pod — no live thief exists there.
        sizes2 = master_ops.gather_sizes(q, worker_axis=axis_name)
        with jax.named_scope("master.plan"):
            rplan = recovery_plan(sizes2, dead_intra,
                                  max_steal=pol.max_steal, capacity=cap)
            rplan = jnp.where(drop, _noop_plan(pod_size), rplan)
        q, irec = master_ops.superstep(q, pol, axis_name=axis_name,
                                       ops=ops, plan=rplan)

        # (4) Cross-pod recovery: a dead POD escalates — each ring row w
        # drains the dead pods' lane-w rings into the emptiest live
        # pod's lane-w, riding the same exchange over the pod axis.
        dead_row = dead2d[:, w_idx]                    # (n_pods,) my row
        sizes_row = lax.all_gather(q.size, pod_axis)
        with jax.named_scope("master.plan"):
            xplan = recovery_plan(sizes_row, pod_dead,
                                  max_steal=pol.max_steal, capacity=cap,
                                  thief_ok=~dead_row)
            xplan = jnp.where(drop, _noop_plan(n_pods), xplan)
        q, xrec = master_ops.superstep(q, pol, axis_name=pod_axis,
                                       ops=ops, plan=xplan)

        # Accounting, reduce_round_stats-exact: intra recovery adds to
        # the per-pod intra counters; per-row cross-pod recovery counts
        # are summed over the rows of a pod (replicated across pods) and
        # folded onto lane-0 so the xpod fields stay nonzero only on
        # representatives.  bytes stay PER-LANE (physical injection).
        is_rep = w_idx == 0
        xrec_nt = lax.psum(xrec.n_transferred, axis_name)
        xrec_ns = lax.psum(xrec.n_steals, axis_name)
        stats = intra._replace(
            sizes_after=xrec.sizes_after,
            n_transferred=intra.n_transferred + irec.n_transferred,
            n_steals=intra.n_steals + irec.n_steals,
            bytes_moved=intra.bytes_moved + irec.bytes_moved,
            n_transferred_xpod=(pod_stats.n_transferred
                                + jnp.where(is_rep, xrec_nt, 0)),
            n_steals_xpod=(pod_stats.n_steals
                           + jnp.where(is_rep, xrec_ns, 0)),
            bytes_moved_xpod=pod_stats.bytes_moved + xrec.bytes_moved,
        )
        return q, carry, stats

    return hier_lane if hierarchical else flat_lane
