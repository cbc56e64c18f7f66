"""The unified executor: adaptive rebalancing rounds over queue lanes.

``StealRuntime`` is the one entry point every workload drives (DD
branch-and-bound, serving admission replay, the Fig. 7/8 benchmarks).  A
*round* is::

    [worker body: pop_bulk -> compute -> push]   (optional, per lane)
    master.superstep / hierarchical_superstep    (bulk steal rebalance)

compiled ONCE as a single jitted function.  Four properties make it the
production hot path:

* **One queue contract, pluggable backends** — the runtime resolves a
  :class:`repro.core.ops.BulkOps` backend ONCE at construction
  (``backend="auto"`` consults the kernel geometry predicates; the
  resolved object is exposed as :attr:`StealRuntime.ops`).  Every
  victim-side block detach, thief-side splice and worker-body queue op
  goes through that backend — the Pallas ring kernels when the routing
  resolves to them, the jnp reference oracle otherwise.
* **Donated queue state** — the round function donates the stacked
  ``QueueState``, so XLA aliases the ring buffers input->output and the
  rebalance updates in place instead of copying the full-capacity rings
  every superstep (donation is skipped on backends without support).
* **Traced proportion** — the steal proportion enters as a scalar
  argument, so the :class:`~repro.runtime.adaptive.AdaptiveController`
  can re-tune it every round with zero recompiles.
* **Fused supersteps** — :meth:`StealRuntime.run_fused` ``lax.scan``s k
  rounds in ONE dispatch: the adaptive update runs on device inside the
  scan carry and per-round telemetry is stacked ``(k, ...)`` and read
  back once, so autotuning never leaves the device and k rounds cost one
  dispatch + one host sync instead of k of each.  With
  ``until_drained=True`` the scan becomes a ``lax.while_loop`` that
  stops on device the moment every lane is empty and reports the rounds
  actually executed.

Worker bodies run *under vmap/shard_map* with the runtime's axis name in
scope, so they may use collectives (e.g. ``lax.pmax`` for a global
incumbent) exactly like ``core.dd.parallel`` does.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from repro.core import master as master_ops
from repro.core import ops as bulk_ops
from repro.core.policy import StealPolicy
from repro.core.sharded_queue import make_sharded_queues
from repro.obs import spans
from repro.runtime import resilience
from repro.runtime.adaptive import (AdaptiveConfig, AdaptiveController,
                                    adaptive_update)
from repro.runtime.resilience import FaultPlan, FaultState
from repro.runtime.telemetry import (Telemetry, item_nbytes,
                                     reduce_round_stats)

Pytree = Any
WorkerFn = Callable[[bulk_ops.QueueState, Pytree],
                    Tuple[bulk_ops.QueueState, Pytree]]

__all__ = ["StealRuntime", "LiftedJit", "make_lane_step"]


class LiftedJit:
    """``jax.jit(fn, donate_argnums=...)``, except that the arrays ``fn``
    closes over (a decode body's model parameters, say) reach the
    compiled program as arguments instead of being embedded in it as
    constants.  A program that embeds gigabytes of weights cannot be
    compiled.  Each new signature of the arguments is traced once to a
    jaxpr, whose constants are then passed on every call.

    The constants are placed once, when their signature is traced:
    committed with ``const_sharding`` where one is given (the replicated
    sharding a mesh program reads them with), on the default device
    otherwise.  A call then hands them to the program as they are, with
    no copy."""

    def __init__(self, fn: Callable, donate_argnums: tuple = (),
                 const_sharding=None):
        self._fn = fn
        self._const_sharding = const_sharding
        self._traced: Dict[Any, Tuple[Any, Any]] = {}
        self._jit = jax.jit(self._eval, static_argnums=(0,),
                            donate_argnums=tuple(i + 2
                                                 for i in donate_argnums))

    def _eval(self, key, consts, *args):
        jaxpr, out_tree = self._traced[key][0]
        out = jax.core.eval_jaxpr(jaxpr, consts,
                                  *jax.tree_util.tree_leaves(args))
        return jax.tree_util.tree_unflatten(out_tree, out)

    @staticmethod
    def _signature(args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        return (tree, tuple(jax.typeof(x) for x in leaves))

    def _key(self, args):
        key = self._signature(args)
        if key not in self._traced:
            closed, shape = jax.make_jaxpr(self._fn, return_shape=True)(
                *args)
            self._traced[key] = ((closed.jaxpr,
                                  jax.tree_util.tree_structure(shape)),
                                 jax.device_put(closed.consts,
                                                self._const_sharding))
        return key

    def __call__(self, *args):
        key = self._signature(args)
        if key in self._traced:
            return self._jit(key, self._traced[key][1], *args)
        # A new signature: trace it, then the first call lowers and
        # compiles it (or loads it from the persistent cache).
        with spans.span("steal:compile"):
            spans.count("steal.compiles")
            self._key(args)
            return self._jit(key, self._traced[key][1], *args)

    def lower(self, *args) -> jax.stages.Lowered:
        """The program a call with ``args`` runs, for inspection."""
        key = self._key(args)
        return self._jit.lower(key, self._traced[key][1], *args)

    def _cache_size(self) -> int:
        return self._jit._cache_size()


class ReadbackLayout:
    """Everything the host reads after a fused dispatch, in ONE int32
    vector: the executed round count, the final proportion, then each
    telemetry leaf flattened (4-byte dtypes other than int32, the float32
    proportions among them, are bit-cast, so every value comes back
    exactly).  Each read from the device is a hand-off to the runtime's
    threads that costs hundreds of microseconds on the chip whatever its
    size, so a dispatch makes one read, not one a leaf.

    :meth:`pack` runs while the block is traced and records where each
    leaf sits from the traced shapes; :meth:`unpack` rebuilds the
    telemetry tree from the host copy with slices and views, reading
    nothing more from the device."""

    def __init__(self):
        self._tree = None
        self._leaves: Tuple[Tuple[int, int, tuple, np.dtype], ...] = ()

    @staticmethod
    def _bits(x) -> jax.Array:
        if x.dtype != jnp.int32:
            x = lax.bitcast_convert_type(x, jnp.int32)
        return x.reshape(-1)

    def pack(self, rounds, p_final, tele) -> jax.Array:
        leaves, self._tree = jax.tree_util.tree_flatten(tele)
        fields, at = [], 2
        for x in leaves:
            stop = at + x.size
            fields.append((at, stop, tuple(x.shape), np.dtype(x.dtype)))
            at = stop
        self._leaves = tuple(fields)
        return jnp.concatenate(
            [self._bits(x) for x in (rounds, p_final, *leaves)])

    def unpack(self, buf: np.ndarray) -> Tuple[int, float, Pytree]:
        """``(rounds, p_final, tele)`` from the host copy of the buffer."""
        leaves = [buf[a:b].view(dtype).reshape(shape)
                  for a, b, shape, dtype in self._leaves]
        return (int(buf[0]), float(buf[1:2].view(np.float32)[0]),
                jax.tree_util.tree_unflatten(self._tree, leaves))


class FusedProgram(LiftedJit):
    """A fused block ``(qs, carry, p0, ctx0) -> (qs, carry, p, ctx,
    packed)`` and the :class:`ReadbackLayout` its ``packed`` output
    follows.  ``p`` and ``ctx`` are the proportion and the fault context
    the next round would start from, left on the devices."""

    def __init__(self, fn: Callable, layout: ReadbackLayout,
                 donate_argnums: tuple = (), const_sharding=None):
        super().__init__(fn, donate_argnums, const_sharding)
        self.layout = layout


def make_lane_step(policy: StealPolicy, ops: bulk_ops.BulkOps,
                   worker_fn: Optional[WorkerFn], *, axis_name: str,
                   pod_axis: Optional[str] = None,
                   hierarchical: bool = False,
                   fault: bool = False) -> Callable:
    """The mode-agnostic round body for ONE lane:
    ``(q, carry, proportion, ctx) -> (q, carry, stats)``.

    This is the single definition of what a round IS — optional worker
    body, then the rebalancing superstep (flat over ``axis_name``, or
    hierarchical over ``(pod_axis, axis_name)``), with the steal
    proportion injected as a traced scalar.  Both executors build their
    execution mode AROUND it: :class:`StealRuntime` maps it with
    ``jax.vmap(axis_name=...)`` over stacked lanes on one device, and
    :class:`repro.distributed.MeshStealRuntime` runs it per-shard under
    ``shard_map`` over real mesh axes of the same names.  Because the
    collectives resolve through the axis names either way, the two modes
    execute the identical computation — the parity tests assert the
    results are bit-identical.

    ``ctx`` is the fault context (see :mod:`repro.runtime.resilience`):
    a bare int32 round index when ``fault=False`` (ignored by the lane
    body, so the compiled round is unchanged), or the replicated fault
    schedule dict when ``fault=True`` — then the returned lane is
    :func:`~repro.runtime.resilience.make_resilient_lane`, which also
    runs the dead-ring recovery superstep each round (intra-pod recovery
    plus the cross-pod dead-POD escalation when ``hierarchical=True``).

    Each phase of the round runs under a named scope (``worker_body``,
    ``master.sizes|plan|exchange|splice``, and ``queue.*`` inside the
    queue ops), so a device trace attributes each instruction's time to
    its phase (DESIGN.md §11).
    """
    if fault:
        return resilience.make_resilient_lane(policy, ops, worker_fn,
                                              axis_name=axis_name,
                                              pod_axis=pod_axis,
                                              hierarchical=hierarchical)

    def lane(q, carry, proportion, ctx):
        del ctx  # round index only; same signature as the fault lane
        if worker_fn is not None:
            with jax.named_scope("worker_body"):
                q, carry = worker_fn(q, carry)
        pol = dataclasses.replace(policy, proportion=proportion)
        if hierarchical:
            q, stats = master_ops.hierarchical_superstep(
                q, pol, worker_axis=axis_name, pod_axis=pod_axis, ops=ops)
        else:
            q, stats = master_ops.superstep(q, pol, axis_name=axis_name,
                                            ops=ops)
        return q, carry, stats

    return lane


class StealRuntime:
    """Owns W per-worker queues and drives adaptive rebalancing rounds.

    Args:
      n_workers: number of queue lanes (vmap lanes on one device; one per
        device under shard_map — the round function is mode-agnostic).
      capacity: static ring capacity per lane.
      item_spec: payload pytree of ``ShapeDtypeStruct``/arrays per item.
      policy: base :class:`StealPolicy`; its ``proportion`` seeds the
        adaptive controller, the rest (watermarks, ``max_steal``) is
        static.
      adaptive: enable the steal-proportion feedback loop (default on).
      backend: optional override for the :class:`~repro.core.ops.BulkOps`
        backend (a registry name or an existing instance).  When omitted
        the runtime honours ``policy.backend`` (default ``"auto"``), so
        a pinned ``StealPolicy(backend="reference")`` selects the same
        implementation here as it does in a standalone
        ``master.superstep``.  ``"auto"`` resolves the kernel routing
        here, once, from the queue geometry (capacity,
        ``policy.max_steal``, and ``max_pop`` for worker-body bulk pops)
        and honours the ``REPRO_QUEUE_BACKEND`` environment override.
        The resolved backend is exposed as :attr:`ops` so worker bodies
        drive the exact same routing.
      max_pop: geometry hint for ``"auto"``: the largest ``max_n`` worker
        bodies will pass to ``ops.pop_bulk`` (None leaves the bulk-pop on
        the reference path).
      pod_size: if set, lanes are grouped into pods of this size and each
        round runs :func:`master.hierarchical_superstep` (intra-pod, then
        cross-pod via lane-0 representatives).
      fault_plan: arm the resilience layer with a deterministic
        :class:`~repro.runtime.resilience.FaultPlan` (kill/delay/drop
        schedule).  An EMPTY ``FaultPlan()`` schedules nothing but still
        arms the machinery — live :meth:`kill_lane`/:meth:`revive_lane`
        (planned eviction, shrink/grow) and the per-round recovery
        superstep that drains dead rings at proportion 1.0.  ``None``
        (default) leaves the compiled round byte-identical to the
        fault-free executor.  Composes with ``pod_size``: on the
        hierarchical grid a dead LANE drains intra-pod, an entirely
        dead POD escalates to a cross-pod recovery plan.
      programs: the table the compiled rounds and fused blocks are kept
        in, keyed by ``worker_fn`` (and ``k`` for fused blocks).  A
        caller that builds runtimes of one geometry and one policy again
        and again passes the same table to each, with the same
        ``worker_fn`` object, and the programs are traced and compiled
        once; queues, controller, telemetry and ``rounds_run`` stay per
        runtime.  Only runtimes that would trace the same program may
        share a table.  ``None`` (default): a table of this runtime's
        own.
    """

    def __init__(self, n_workers: int, capacity: int, item_spec: Pytree, *,
                 policy: Optional[StealPolicy] = None,
                 adaptive: bool = True,
                 adaptive_config: Optional[AdaptiveConfig] = None,
                 backend: str | bulk_ops.BulkOps | None = None,
                 max_pop: Optional[int] = None,
                 axis_name: str = "workers",
                 pod_size: Optional[int] = None,
                 pod_axis: str = "pods",
                 queue_sharding=None,
                 fault_plan: Optional[FaultPlan] = None,
                 programs: Optional[Dict[Any, Callable]] = None):
        if pod_size is not None and n_workers % pod_size != 0:
            raise ValueError(
                f"n_workers={n_workers} not divisible by pod_size={pod_size}")
        self.n_workers = int(n_workers)
        self.capacity = int(capacity)
        self.item_spec = item_spec
        self.axis_name = axis_name
        self.pod_size = pod_size
        self.pod_axis = pod_axis
        base = policy or StealPolicy()
        if backend is None:
            backend = base.backend  # honour a pinned policy.backend
        self.ops = bulk_ops.make_ops(
            backend, capacity=self.capacity, max_push=base.max_steal,
            max_pop=max_pop, max_steal=base.max_steal)
        self.policy = dataclasses.replace(base, backend=self.ops.name)
        # ``queue_sharding`` (a NamedSharding over the lane axis) places
        # each lane's ring on its owning device from the first byte —
        # what the mesh subclass passes; the stack is built sharded, not
        # built dense and re-placed.
        self.queues = make_sharded_queues(n_workers, capacity, item_spec,
                                          sharding=queue_sharding)
        # Sanitizer wiring: REPRO_CHECK=1 (or an explicitly checked
        # backend) turns on per-round invariant checkpoints — stats
        # arithmetic plus, for pure rebalancing rounds, exact multiset
        # conservation of the live items across lanes.
        from repro.analysis.sanitize import CheckedBulkOps

        self._check = isinstance(self.ops, CheckedBulkOps)
        self.controller = (AdaptiveController(self.policy, adaptive_config)
                           if adaptive else None)
        self.telemetry = Telemetry(item_bytes=item_nbytes(item_spec),
                                   capacity=capacity)
        self.rounds_run = 0
        # The proportion and the round index the last fused dispatch left
        # on the devices, each beside the host value it stands for (the
        # proportion's float32 bits, the round): see _fused_scalars.
        self._held_p: Tuple[Any, Any] = (None, None)
        self._held_ctx: Tuple[Any, Any] = (None, None)
        self._compiled: Dict[Any, Callable] = (
            {} if programs is None else programs)
        # Resilience: the host-side fault schedule (None = machinery off,
        # zero trace-structure change) and the snapshot cadence.
        if fault_plan is not None:
            # The dead-lane sentinel (low_watermark + 1) must be neither
            # idle-eligible nor a victim, or masked plans would route
            # work into corpses.
            lo = self.policy.low_watermark + 1
            hi = max(self.policy.high_watermark, self.policy.queue_limit)
            if not (self.policy.low_watermark < lo < hi):
                raise ValueError(
                    f"fault injection needs low_watermark + 1 ="
                    f" {lo} strictly between low_watermark and"
                    f" max(high_watermark, queue_limit) = {hi}")
            self.fault: Optional[FaultState] = FaultState(fault_plan,
                                                          self.n_workers)
            if fault_plan.kills:
                self.telemetry.record_fault("planned_kill",
                                            len(fault_plan.kills))
        else:
            self.fault = None
        # Automatic failure detection (attach_detector): None = off.
        self.detector = None
        self._snapshot_dir: Optional[str] = None
        self._snapshot_every = 0
        self._snapshot_keep = 3
        self._last_snapshot_round = -1

    # -- state access --------------------------------------------------------

    @property
    def proportion(self) -> float:
        """The steal proportion the NEXT round will use (including any
        temporary straggler boost the controller is applying)."""
        return (self.controller.effective_proportion if self.controller
                else self.policy.proportion)

    def sizes(self) -> np.ndarray:
        return np.asarray(self.queues.size)

    def total_size(self) -> int:
        return int(self.sizes().sum())

    # -- host-side seeding / draining ---------------------------------------

    def lane(self, worker: int) -> bulk_ops.QueueState:
        """Lane ``worker``'s queue, for a host-level op (``ops.push``,
        ``ops.pop_bulk``...) whose result goes back by :meth:`set_lane`."""
        return jax.tree_util.tree_map(lambda x: x[worker], self.queues)

    def set_lane(self, worker: int, qi: bulk_ops.QueueState) -> None:
        self.queues = jax.tree_util.tree_map(
            lambda full, one: full.at[worker].set(one), self.queues, qi)

    def push(self, worker: int, batch: Pytree, n: int) -> int:
        """Owner-side bulk push into one lane (host-level seeding)."""
        qi, pushed = self.ops.push(self.lane(worker), batch, jnp.int32(n))
        self.set_lane(worker, qi)
        return int(pushed)

    def drain(self) -> list:
        """Pop every lane dry (host-level; for tests/inspection).  Returns
        a list of per-lane item lists, newest-first per lane."""
        out = []
        for i in range(self.n_workers):
            qi = self.lane(i)
            lane = []
            while int(qi.size) > 0:
                qi, item, valid = self.ops.pop(qi)
                assert bool(valid)
                lane.append(jax.tree_util.tree_map(np.asarray, item))
            out.append(lane)
            self.set_lane(i, qi)
        return out

    # -- resilience: live faults, stragglers ---------------------------------

    def _require_fault(self) -> FaultState:
        if self.fault is None:
            raise RuntimeError(
                "fault layer not armed — construct the runtime with "
                "fault_plan=FaultPlan() to enable kill/revive")
        return self.fault

    def kill_lane(self, lane: int, at_round: Optional[int] = None) -> None:
        """Declare lane ``lane`` dead from round ``at_round`` (default:
        the next round).  Its worker body stops producing, it leaves
        every plan, and the recovery superstep drains its ring into the
        survivors at proportion 1.0 over the following rounds.  Pure
        host-side value mutation — no recompile.

        Killing an already-dead lane raises: silently rescheduling a
        corpse's kill round would rewrite replay history (the schedule is
        the determinism contract) and mask double-kill bugs in callers."""
        fault = self._require_fault()
        at = self.rounds_run if at_round is None else at_round
        if bool(fault.dead_at(max(at, self.rounds_run))[lane]):
            raise ValueError(
                f"lane {lane} is already dead (kill_round="
                f"{int(fault.kill_round[lane])}); revive_lane first")
        fault.kill(lane, at)
        self.telemetry.record_fault("kill", lane=lane)

    def revive_lane(self, lane: int) -> None:
        """Re-admit a killed lane (grow / end of eviction): it rejoins
        plans from the next round with whatever its (drained) ring holds.
        Any accumulated straggler penalty for the lane is cleared — a
        revived lane starts with a clean bill of health, not
        pre-penalized by its past life."""
        self._require_fault().revive(lane)
        if self.controller is not None:
            self.controller.clear_straggler(lane)
        if self.detector is not None:
            self.detector.revive(lane)
        self.telemetry.record_fault("revive", lane=lane)

    def dead_lanes(self) -> np.ndarray:
        """(W,) bool: lanes dead as of the next round to run."""
        if self.fault is None:
            return np.zeros((self.n_workers,), bool)
        return self.fault.dead_at(self.rounds_run)

    def note_straggler(self, rounds: int = 4, factor: float = 1.5,
                       lane: Optional[int] = None) -> None:
        """Record a detected straggler (``train.fault.StragglerMonitor``
        wiring): counts into telemetry and temporarily boosts the
        adaptive steal proportion so the master rebalances harder while
        the slow lane lags.  ``lane`` attributes the boost so a later
        :meth:`revive_lane` can clear exactly that lane's penalty."""
        self.telemetry.record_fault("straggler", lane=lane)
        if self.controller is not None:
            self.controller.flag_straggler(rounds=rounds, factor=factor,
                                           lane=lane)

    # -- resilience: automatic failure detection ------------------------------

    def attach_detector(self, policy=None) -> "FailureDetector":
        """Arm the automatic failure detector: per-lane delay streaks from
        the fault schedule (or any external observer calling
        ``detector.observe``) escalate suspected -> dead through ONE
        policy — a suspected lane gets a :meth:`note_straggler`
        proportion boost, a lane past ``dead_after`` consecutive slow
        rounds gets a real :meth:`kill_lane` and its ring drains through
        the recovery superstep.  Requires the fault layer
        (``fault_plan=``).  Returns the detector (also at
        :attr:`detector`)."""
        from repro.runtime.detector import DetectorPolicy, FailureDetector

        self._require_fault()
        pol = policy or DetectorPolicy()

        def on_suspect(lane: int) -> None:
            self.telemetry.record_fault("suspect", lane=lane)
            self.note_straggler(rounds=pol.boost_rounds,
                                factor=pol.boost_factor, lane=lane)

        def on_dead(lane: int) -> None:
            # The user (or an overlapping schedule) may have killed the
            # lane already — the detector's verdict is then moot.
            if not bool(self.dead_lanes()[lane]):
                self.kill_lane(lane)
                self.telemetry.record_fault("auto_kill", lane=lane)

        def on_revive(lane: int) -> None:
            if self.controller is not None:
                self.controller.clear_straggler(lane)

        self.detector = FailureDetector(self.n_workers, pol,
                                        on_suspect=on_suspect,
                                        on_dead=on_dead,
                                        on_revive=on_revive)
        return self.detector

    def _feed_detector(self, round0: int, n_rounds: int,
                       wall_s: Optional[float] = None) -> None:
        """Feed the armed detector one observation per (round, live lane)
        from the replayed delay schedule.  Host-side replay of the same
        replicated schedule the lanes traced — deterministic, so vmap
        and mesh runs convert the same delay windows into the same
        kills at the same rounds (replay parity is preserved).

        When ``DetectorPolicy.wall_clock`` is set, the measured dispatch
        wall (``wall_s``, covering ``n_rounds`` rounds) ALSO feeds each
        live lane's rolling wall baseline via ``observe_wall`` — real
        slowness detection on the runtime path.  The dispatch is one
        SPMD program, so the wall is a collective signal: it cannot
        finger the slow lane, it flags rounds whose whole dispatch ran
        slow against each lane's own history (suspected -> boost; never
        a kill unless ``wall_kill``).  Off by default, keeping CI replay
        determinism and the vmap/mesh parity tests untouched."""
        if self.detector is None or self.fault is None:
            return
        f = self.fault
        for r in range(round0, round0 + n_rounds):
            dead = f.dead_at(r)
            slow = (f.delay_from <= r) & (r < f.delay_until)
            for w in range(self.n_workers):
                if dead[w]:
                    continue  # corpses emit no heartbeats at all
                self.detector.observe(w, bool(slow[w]))
        pol = self.detector.policy
        if (wall_s is not None and n_rounds > 0
                and getattr(pol, "wall_clock", False)):
            per_round = wall_s / n_rounds
            dead = f.dead_at(round0 + n_rounds)
            for w in range(self.n_workers):
                if not dead[w]:
                    self.detector.observe_wall(w, per_round)

    def _controller_sizes(self, sizes: np.ndarray) -> np.ndarray:
        """The size vector the host controller servos on: dead lanes
        masked to the sentinel (mirrors the on-device masking in the
        fused path)."""
        if self.fault is None:
            return sizes
        dead = self.fault.dead_at(self.rounds_run + 1)
        return np.where(dead, np.int32(self.policy.low_watermark + 1),
                        np.asarray(sizes, np.int32))

    # -- resilience: queue snapshot / restore --------------------------------

    def state_dict(self) -> Dict[str, Any]:
        """The checkpointable runtime state: the stacked queues, the
        servo proportion (un-boosted) and the global round counter —
        plus the fault schedule when armed.  Snapshots are taken only at
        round boundaries, which are exactly the consistency points where
        conservation holds (no item is mid-exchange)."""
        if self.controller is not None:
            p = self.controller.proportion
        else:
            p = self.policy.proportion
        out: Dict[str, Any] = {
            "queues": self.queues,
            "proportion": jnp.float32(p),
            "rounds_run": jnp.int32(self.rounds_run),
        }
        if self.fault is not None:
            out["fault"] = {k: jnp.asarray(v)
                            for k, v in self.fault.state_dict().items()}
        return out

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.queues = jax.tree_util.tree_map(jnp.asarray, state["queues"])
        p = float(np.asarray(state["proportion"]))
        if self.controller is not None:
            self.controller.proportion = p
            self.controller.history.append(p)
        self.rounds_run = int(np.asarray(state["rounds_run"]))
        if self.fault is not None and "fault" in state:
            self.fault.load_state({k: np.asarray(v)
                                   for k, v in state["fault"].items()})

    def _state_shardings(self, template: Dict[str, Any]):
        """Shardings for elastic restore of :meth:`state_dict` — None in
        the single-device runtime (plain host arrays); the mesh runtime
        overrides this to place queue lanes on their owning devices."""
        del template
        return None

    def save_state(self, ckpt_dir: str, *, keep: int = 3) -> int:
        """Atomic queue snapshot at the current round boundary (written
        via :mod:`repro.train.checkpoint`: tmp dir + rename, keep-k GC).
        Returns the step (= ``rounds_run``) it was saved under."""
        from repro.train import checkpoint

        extra = {"n_workers": self.n_workers, "capacity": self.capacity,
                 "fault_events": dict(self.telemetry.fault_events),
                 "straggler_steps": self.telemetry.straggler_steps}
        checkpoint.save(ckpt_dir, self.rounds_run, self.state_dict(),
                        extra=extra, keep=keep)
        return self.rounds_run

    def restore_state(self, ckpt_dir: str, *, step: Optional[int] = None
                      ) -> int:
        """Restore queues/proportion/round-counter (and fault schedule)
        from the latest (or given) snapshot.  Elastic: the checkpoint
        holds full host arrays, and :meth:`_state_shardings` re-places
        them onto THIS runtime's devices — a snapshot written under an
        8-device mesh restores onto 1 device or a different mesh shape.
        Returns the restored round index."""
        from repro.train import checkpoint

        template = self.state_dict()
        state, _step, extra = checkpoint.restore(
            ckpt_dir, template, step=step,
            shardings=self._state_shardings(template))
        self.load_state_dict(state)
        for kind, n in (extra.get("fault_events") or {}).items():
            self.telemetry.fault_events.setdefault(kind, 0)
            self.telemetry.fault_events[kind] = max(
                self.telemetry.fault_events[kind], int(n))
        self.telemetry.straggler_steps = max(
            self.telemetry.straggler_steps,
            int(extra.get("straggler_steps", 0)))
        self.telemetry.record_fault("restore")
        self._last_snapshot_round = self.rounds_run
        return self.rounds_run

    def attach_snapshots(self, ckpt_dir: str, *, every: int = 8,
                         keep: int = 3) -> None:
        """Snapshot the queue state every ``every`` rounds (checked after
        each :meth:`round` / :meth:`run_fused` dispatch — always at a
        round boundary, never mid-exchange)."""
        self._snapshot_dir = ckpt_dir
        self._snapshot_every = max(int(every), 1)
        self._snapshot_keep = keep
        self._last_snapshot_round = self.rounds_run

    def _maybe_snapshot(self) -> None:
        if self._snapshot_dir is None:
            return
        if self.rounds_run - self._last_snapshot_round >= self._snapshot_every:
            self.save_state(self._snapshot_dir, keep=self._snapshot_keep)
            self._last_snapshot_round = self.rounds_run

    # -- the round -----------------------------------------------------------

    def _lane_step(self, worker_fn: Optional[WorkerFn]) -> Callable:
        """The shared one-lane round body (see :func:`make_lane_step`)."""
        return make_lane_step(self.policy, self.ops, worker_fn,
                              axis_name=self.axis_name,
                              pod_axis=self.pod_axis,
                              hierarchical=self.pod_size is not None,
                              fault=self.fault is not None)

    def _ctx(self, round0: int):
        """The fault context for a dispatch starting at global round
        ``round0``: the replicated schedule dict when the fault layer is
        armed, a bare int32 round index otherwise (both are traced, so
        host-side schedule mutation never recompiles)."""
        if self.fault is not None:
            return self.fault.ctx(round0)
        return jnp.int32(round0)

    def _fused_scalars(self, round0: int):
        """The proportion and the fault context of a fused dispatch that
        starts at global round ``round0``.

        The previous dispatch left both on the devices.  They are handed
        on as they are while the host still holds the values they stand
        for, so a steady run of dispatches puts nothing.  A value the host
        changed (a straggler boost, a restore, rounds run by
        :meth:`round`) is put afresh, placed where the block leaves its
        own, so both kinds of call run one executable.  An armed fault
        layer's context is the host's schedule and is put every time.
        ``steal.enqueue_puts`` counts the puts."""
        sharding = self._replicated_sharding()
        value = np.float32(self.proportion)
        bits, p = self._held_p
        if bits != value.view(np.uint32):
            p = jax.device_put(value, sharding)
            spans.count("steal.enqueue_puts")
        held_round, ctx = self._held_ctx
        if self.fault is not None:
            ctx = self._ctx(round0)
            spans.count("steal.enqueue_puts")
        elif held_round != round0:
            ctx = jax.device_put(np.int32(round0), sharding)
            spans.count("steal.enqueue_puts")
        return p, ctx

    def _make_step(self, worker_fn: Optional[WorkerFn]) -> Callable:
        """Un-jitted ``(qs, carry, proportion, ctx) -> (qs, carry, stats)``."""
        pod_size = self.pod_size
        axis_name, pod_axis = self.axis_name, self.pod_axis
        lane = self._lane_step(worker_fn)

        if pod_size is None:
            mapped = jax.vmap(lane, axis_name=axis_name,
                              in_axes=(0, 0, None, None))

            def step(qs, carry, proportion, ctx):
                return mapped(qs, carry, proportion, ctx)
        else:
            n_pods = self.n_workers // pod_size
            inner = jax.vmap(lane, axis_name=axis_name,
                             in_axes=(0, 0, None, None))
            outer = jax.vmap(inner, axis_name=pod_axis,
                             in_axes=(0, 0, None, None))

            def step(qs, carry, proportion, ctx):
                split = jax.tree_util.tree_map(
                    lambda x: x.reshape((n_pods, pod_size) + x.shape[1:]),
                    (qs, carry))
                qs2, carry2, stats = outer(*split, proportion, ctx)
                merge = jax.tree_util.tree_map(
                    lambda x: x.reshape((self.n_workers,) + x.shape[2:]),
                    (qs2, carry2, stats))
                return merge

        return step

    @staticmethod
    def _donate_argnums() -> tuple:
        return () if jax.default_backend() == "cpu" else (0,)

    def _replicated_sharding(self):
        """Where a value that every lane reads whole is placed: a worker
        body's closed-over constants, the proportion and the round index.
        ``None`` here, JAX's default device uncommitted, which is where
        the vmapped blocks leave their own outputs; the mesh runtime
        replicates them over its devices."""
        return None

    def _compile(self, worker_fn: Optional[WorkerFn]) -> Callable:
        return LiftedJit(self._make_step(worker_fn), self._donate_argnums(),
                         self._replicated_sharding())

    def _compile_fused(self, worker_fn: Optional[WorkerFn], k: int,
                       until_drained: bool = False) -> Callable:
        """One dispatch for k rounds: the superstep scanned on device with
        the adaptive proportion updated as a traced scalar inside the
        carry, telemetry stacked ``(k, ...)`` along the scan axis.  With
        ``until_drained`` the scan becomes a ``lax.while_loop`` over the
        same round body that exits as soon as every lane is empty (checked
        on device, before each round), writing telemetry into
        preallocated ``(k, ...)`` slots and returning the executed round
        count.  The round count, the final proportion and the telemetry
        leave the device packed in one int32 buffer
        (:class:`ReadbackLayout`); the final proportion and the advanced
        fault context also stay on the device, for the next dispatch."""
        step = self._make_step(worker_fn)
        policy, controller = self.policy, self.controller
        config = controller.config if controller else None
        layout = ReadbackLayout()

        def one_round(qs, carry, p, ctx):
            qs, carry, stats = step(qs, carry, p, ctx)
            tele = {"stats": stats, "sizes": qs.size, "proportion": p}
            ctx = resilience.ctx_advance(ctx)
            if controller is not None:
                # Dead lanes advertise the sentinel, never counting as
                # idle thieves (same masking the host controller applies).
                sizes = resilience.mask_sizes(qs.size, ctx, policy)
                p = adaptive_update(p, sizes, policy=policy, config=config)
            return qs, carry, p, ctx, tele

        if not until_drained:
            def fused(qs, carry, p0, ctx0):
                def body(state, _):
                    qs, carry, p, ctx = state
                    qs, carry, p, ctx, tele = one_round(qs, carry, p, ctx)
                    return (qs, carry, p, ctx), tele

                (qs, carry, p, ctx), tele = lax.scan(
                    body, (qs, carry, p0, ctx0), None, length=k)
                return qs, carry, p, ctx, layout.pack(jnp.int32(k), p, tele)

            return FusedProgram(fused, layout, self._donate_argnums(),
                                self._replicated_sharding())

        def fused(qs, carry, p0, ctx0):
            tele_sds = jax.eval_shape(
                lambda a, b, c, d: one_round(a, b, c, d)[4], qs, carry, p0,
                ctx0)
            tele0 = jax.tree_util.tree_map(
                lambda s: jnp.zeros((k,) + tuple(s.shape), s.dtype), tele_sds)

            def cond(state):
                qs, _carry, _p, _ctx, r, _tele = state
                return (r < k) & (jnp.sum(qs.size) > 0)

            def body(state):
                qs, carry, p, ctx, r, tele = state
                qs, carry, p, ctx, t = one_round(qs, carry, p, ctx)
                tele = jax.tree_util.tree_map(
                    lambda buf, v: lax.dynamic_update_index_in_dim(
                        buf, v, r, 0), tele, t)
                return (qs, carry, p, ctx, r + 1, tele)

            qs, carry, p, ctx, r, tele = lax.while_loop(
                cond, body, (qs, carry, p0, ctx0, jnp.int32(0), tele0))
            return qs, carry, p, ctx, layout.pack(r, p, tele)

        return FusedProgram(fused, layout, self._donate_argnums(),
                            self._replicated_sharding())

    # -- observability -------------------------------------------------------

    def metrics(self, registry=None):
        """Poll this runtime into a :class:`repro.obs.metrics.
        MetricsRegistry` (queue depths, steal totals, fault/detector
        census).  Pull-style and side-effect free — call it mid-run at
        any cadence;
        ``registry.to_prometheus()`` / ``.snapshot()`` render it."""
        from repro.obs.metrics import runtime_metrics

        return runtime_metrics(self, registry)

    def _round_counts(self, stats) -> Tuple[int, int, int]:
        """Exact (n_steals, n_transferred, bytes_moved) for one round's
        stats (numpy leaves, leading axis = lanes) — the shared
        :func:`repro.runtime.telemetry.reduce_round_stats` reduction,
        identical for vmap-stacked lanes and shard_map-gathered shards."""
        return reduce_round_stats(stats, n_workers=self.n_workers,
                                  pod_size=self.pod_size)

    def _pre_dispatch_snapshot(self, worker_fn):
        """When the sanitizer is on and the dispatch is a pure rebalance
        (no worker body creating/consuming items), fingerprint the live
        items so the post-dispatch check can assert exact conservation."""
        if not self._check or worker_fn is not None:
            return None
        from repro.analysis import sanitize

        return sanitize.queues_fingerprint(self.queues)

    def _post_dispatch_checks(self, round_stats, snap, *, context) -> None:
        """Sanitizer checkpoint after a dispatch's host read-back: stats
        arithmetic per round, multiset conservation for pure rebalances,
        then surface anything the in-trace callbacks recorded."""
        from repro.analysis import sanitize

        for stats_r in round_stats:
            sanitize.check_round_stats(
                stats_r, n_workers=self.n_workers, capacity=self.capacity,
                pod_size=self.pod_size, context=context)
        if snap is not None:
            sanitize.check_conserved(
                snap, sanitize.queues_fingerprint(self.queues),
                context=context)
        sanitize.raise_pending(context)

    def round(self, worker_fn: Optional[WorkerFn] = None,
              carry: Optional[Pytree] = None
              ) -> Tuple[Pytree, master_ops.RebalanceStats]:
        """Run one round; feeds telemetry and the adaptive controller.

        ``carry`` is a pytree with a leading ``(n_workers,)`` axis handed
        to ``worker_fn`` per lane (a zero placeholder when omitted).
        Returns ``(carry_out, stats)``.

        The compiled round is cached by ``worker_fn`` *object identity*:
        pass the same function object every round (close over config
        once, outside the loop) — a fresh lambda/partial per call would
        recompile the superstep every round.
        """
        with spans.span("steal:round"):
            with spans.span("steal:enqueue"):
                fn = self._compiled.get(worker_fn)
                if fn is None:
                    fn = self._compiled[worker_fn] = self._compile(worker_fn)
                if carry is None:
                    carry = jnp.zeros((self.n_workers,), jnp.int32)
                snap = self._pre_dispatch_snapshot(worker_fn)
                proportion = self.proportion
                args = (self.queues, carry, jnp.float32(proportion),
                        self._ctx(self.rounds_run))
                t0 = time.perf_counter()
                self.queues, carry, stats = fn(*args)
            with spans.span("steal:wait"):
                sizes = self.sizes()
                wall_s = time.perf_counter() - t0
            with spans.span("steal:readback"):
                n_steals, n_transferred, bytes_moved = self._round_counts(
                    stats)
            with spans.span("steal:record"):
                spans.count("steal.dispatches")
                spans.count("steal.rounds")
                spans.count("steal.thefts", n_steals)
                spans.count("steal.items_moved", n_transferred)
                if self._check:
                    self._post_dispatch_checks(
                        [jax.tree_util.tree_map(np.asarray, stats)], snap,
                        context="StealRuntime.round")
                if self.controller is not None:
                    self.controller.update(self._controller_sizes(sizes))
                self.telemetry.record(sizes=sizes, n_steals=n_steals,
                                      n_transferred=n_transferred,
                                      proportion=proportion,
                                      bytes_moved=bytes_moved)
                r0 = self.rounds_run
                self.rounds_run += 1
                self._feed_detector(r0, 1, wall_s=wall_s)
                self._maybe_snapshot()
        return carry, stats

    def run_fused(self, k: int, worker_fn: Optional[WorkerFn] = None,
                  carry: Optional[Pytree] = None, *,
                  until_drained: bool = False):
        """Run up to ``k`` rounds in ONE device dispatch.

        Versus ``k`` calls to :meth:`round`, this removes ``k - 1``
        dispatch + host-sync round trips: the queue state is donated and
        threaded through the on-device loop, the adaptive proportion is
        updated on device as a traced scalar
        (:func:`repro.runtime.adaptive.adaptive_update` — the same
        float32 computation the host controller runs, so the trajectory
        is identical), and per-round telemetry is stacked ``(k, ...)``
        and read back, with the round count and the final proportion, in
        one device-to-host transfer at the end.  The final proportion and
        the round index also stay on the devices and start the next
        dispatch, so a steady run puts nothing on enqueue
        (:meth:`_fused_scalars`).

        With ``until_drained=False`` (default) the block is a
        ``lax.scan`` of exactly ``k`` rounds, returning
        ``(carry_out, stats)`` where ``stats`` leaves carry a leading
        ``(k,)`` round axis.  With ``until_drained=True`` the block is a
        ``lax.while_loop`` that exits early once every lane is empty
        (checked on device before each round — a drained workload costs
        zero no-op rounds) and returns ``(carry_out, stats, rounds)``
        where ``rounds <= k`` is the number actually executed and
        ``stats`` leaves carry a leading ``(rounds,)`` axis.

        The same caching rule as :meth:`round` applies: pass the same
        ``worker_fn`` object every call — the compiled block is cached
        by ``(worker_fn, k, until_drained)``.
        """
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        with spans.span("steal:run_fused"):
            with spans.span("steal:enqueue"):
                key = ("fused", worker_fn, k, until_drained)
                fn = self._compiled.get(key)
                if fn is None:
                    fn = self._compiled[key] = self._compile_fused(
                        worker_fn, k, until_drained)
                if carry is None:
                    carry = jnp.zeros((self.n_workers,), jnp.int32)
                snap = self._pre_dispatch_snapshot(worker_fn)
                round0 = self.rounds_run
                args = (self.queues, carry, *self._fused_scalars(round0))
                t0 = time.perf_counter()
                self.queues, carry, p_dev, ctx_dev, packed = fn(*args)
            with spans.span("steal:wait"):
                # The dispatch's one device-to-host read: it waits for the
                # block and brings every value the host needs.
                packed = np.asarray(packed)
                spans.count("steal.host_reads")
            with spans.span("steal:readback"):
                rounds, p_final, tele = fn.layout.unpack(packed)
            with spans.span("steal:record"):
                wall_s = time.perf_counter() - t0
                spans.count("steal.dispatches")
                spans.count("steal.rounds", rounds)
                stats = self._record_fused(tele, rounds, p_final, wall_s,
                                           snap)
                self._held_p = (np.float32(p_final).view(np.uint32), p_dev)
                self._held_ctx = (round0 + rounds, ctx_dev)
                if until_drained:
                    stats = jax.tree_util.tree_map(lambda x: x[:rounds],
                                                   stats)
        if until_drained:
            return carry, stats, rounds
        return carry, stats

    def _record_fused(self, tele, rounds: int, p_final: float,
                      wall_s: float, snap):
        """Host side of a fused block once its telemetry is read back:
        per-round telemetry records, sanitizer checks, the controller,
        the detector and the snapshot cadence.  Returns the stacked
        per-round stats."""
        stats = tele["stats"]
        thefts = moved = 0
        for r in range(rounds):
            stats_r = jax.tree_util.tree_map(lambda x: x[r], stats)
            n_steals, n_transferred, bytes_moved = self._round_counts(stats_r)
            self.telemetry.record(sizes=tele["sizes"][r],
                                  n_steals=n_steals,
                                  n_transferred=n_transferred,
                                  proportion=float(tele["proportion"][r]),
                                  bytes_moved=bytes_moved)
            thefts += n_steals
            moved += n_transferred
        spans.count("steal.thefts", thefts)
        spans.count("steal.items_moved", moved)
        if self._check:
            self._post_dispatch_checks(
                [jax.tree_util.tree_map(lambda x, _r=r: x[_r], stats)
                 for r in range(rounds)], snap,
                context=f"StealRuntime.run_fused[{rounds} rounds]")
        if self.controller is not None and rounds > 0:
            self.controller.absorb(tele["proportion"][:rounds], p_final)
        r0 = self.rounds_run
        self.rounds_run += rounds
        self._feed_detector(r0, rounds, wall_s=wall_s)
        self._maybe_snapshot()
        return stats

    def lower(self, worker_fn: Optional[WorkerFn] = None,
              carry: Optional[Pytree] = None, *,
              fused: int = 1) -> jax.stages.Lowered:
        """The program :meth:`run` dispatches (one round, or a
        ``fused``-round early-exit block), lowered for the current
        state without running it — e.g. to check that the queue kernels
        are in it (``.compile().as_text()``)."""
        if carry is None:
            carry = jnp.zeros((self.n_workers,), jnp.int32)
        args = (self.queues, carry, jnp.float32(self.proportion),
                self._ctx(self.rounds_run))
        if fused > 1:
            return self._compile_fused(worker_fn, fused, True).lower(*args)
        return self._compile(worker_fn).lower(*args)

    def run(self, worker_fn: Optional[WorkerFn] = None,
            carry: Optional[Pytree] = None, *,
            max_rounds: int = 10_000,
            stop_when_empty: bool = True,
            fused: int = 1) -> Pytree:
        """Drive rounds until the queues drain (or ``max_rounds``).

        With ``fused > 1`` the loop advances up to ``fused`` rounds per
        device dispatch (:meth:`run_fused`); when ``stop_when_empty`` the
        fused block early-exits on device the moment every lane drains,
        so the trailing block never runs no-op rounds.
        """
        rounds = 0
        while rounds < max_rounds:
            if fused > 1:
                k = min(fused, max_rounds - rounds)
                if stop_when_empty:
                    carry, _, executed = self.run_fused(
                        k, worker_fn, carry, until_drained=True)
                    rounds += max(executed, 1)
                    if executed < k:
                        break
                else:
                    carry, _ = self.run_fused(k, worker_fn, carry)
                    rounds += k
            else:
                carry, _ = self.round(worker_fn, carry)
                rounds += 1
                if stop_when_empty and self.total_size() == 0:
                    break
        return carry
