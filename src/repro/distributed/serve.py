"""Device-resident admission: the serving master's queues AS executor lanes.

The host :class:`~repro.serve.scheduler.AdmissionMaster` keeps request
queues in Python objects and runs the steal plan in a loop — fine for a
handful of replicas, but it is exactly the layer the executors already
implement on device.  :class:`RuntimeAdmissionMaster` swaps the host
queues for executor lanes holding request IDs (4 bytes/request): one
ring per replica, admission is one bulk push, and every rebalance round
is a real ``master.superstep`` through
:func:`repro.distributed.launch_runtime` — vmap lanes on one device
(``execution="vmap"``) or one lane per device under shard_map
(``execution="mesh"``).  Request payloads (prompts, outputs) stay on the
host in an id-keyed table; only the IDs ride the rings, so the device
traffic per moved request is constant and tiny while the plan, the
adaptive proportion and the telemetry are the SAME code paths the DD
solver and the benchmarks exercise.

The class implements the master surface :class:`~repro.serve.engine.
ServeCluster` drives (``replicas`` / ``submit`` / ``rebalance_many`` /
``telemetry`` / ``stats``), so ``ServeCluster(execution="mesh")`` is a
drop-in switch.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import StealPolicy
from repro.distributed.launch import launch_runtime
from repro.runtime.adaptive import AdaptiveConfig
from repro.runtime.resilience import FaultPlan

__all__ = ["RuntimeAdmissionMaster", "DeviceReplicaLane"]

_SPEC = jax.ShapeDtypeStruct((), jnp.int32)


class DeviceReplicaLane:
    """One replica's view of its executor lane: the ``ReplicaQueue``
    surface (``load`` / ``pop_wave`` / ``finish_wave``) over ring slot
    ``replica_id`` of the master's runtime."""

    def __init__(self, master: "RuntimeAdmissionMaster", replica_id: int):
        self._master = master
        self.replica_id = replica_id
        self.in_flight = 0
        self.completed = 0
        self.evicted = False

    def __len__(self) -> int:
        return int(self._master.runtime.sizes()[self.replica_id])

    def load(self) -> int:
        return len(self) + self.in_flight

    def pop_wave(self, max_wave: int) -> List:
        """Pop up to ``max_wave`` newest request IDs off this lane —
        ONE owner-side bulk pop, not per-item dispatches — and resolve
        them to :class:`~repro.serve.scheduler.Request` objects, newest
        first (the host queues' LIFO discipline)."""
        rt = self._master.runtime
        i = self.replica_id
        qi, batch, n = rt.ops.pop_bulk(rt.lane(i), int(max_wave),
                                       jnp.int32(max_wave))
        rt.set_lane(i, qi)
        # pop_bulk returns the block oldest-first; reverse for LIFO.
        rids = np.asarray(batch)[: int(n)][::-1]
        wave = [self._master.lookup(int(r)) for r in rids]
        self.in_flight += len(wave)
        return wave

    def finish_wave(self, n: int) -> None:
        self.in_flight -= n
        self.completed += n

    # ``AdmissionMaster.rebalance`` reads ``r.q``; the cluster only ever
    # touches len()/load(), which this object answers itself.
    @property
    def q(self):
        return self


class RuntimeAdmissionMaster:
    """The single stealer + admission router, on executor lanes.

    Args:
      n_replicas: lanes (= devices along the worker mesh axis when
        ``execution="mesh"``).
      policy / adaptive / adaptive_config: as the host master; the
        policy's proportion seeds the runtime's adaptive controller.
      execution: ``"vmap"`` or ``"mesh"`` (see
        :func:`repro.distributed.launch_runtime`).
      capacity: per-lane ring capacity (queued request IDs per replica).
      mesh: optional pinned mesh for ``execution="mesh"``.
      item_spec: per-item ring payload.  The default (a scalar int32)
        is the id-keyed wave mode described above; the decode engine
        (:mod:`repro.serve.decode`) passes the full request-item spec so
        admitted prompts ride the rings and the superstep can steal
        them — when overriding, admit through ``runtime.push`` with
        batches of that spec rather than :meth:`submit`.
      max_pop: owner-side bulk-pop geometry (defaults to the ring
        capacity; the decode engine caps it at its slot count).
      elastic: arm the runtime's fault layer (an empty
        :class:`~repro.runtime.resilience.FaultPlan`) so
        :meth:`evict`/:meth:`readmit` can drain and mask lanes live —
        the default; both execution modes arm it, so vmap/mesh parity
        is preserved.
      backend: queue-op backend of the runtime (default: the policy's,
        ``"auto"``).
    """

    def __init__(self, n_replicas: int,
                 policy: Optional[StealPolicy] = None,
                 adaptive: bool = True,
                 adaptive_config: Optional[AdaptiveConfig] = None, *,
                 execution: str = "vmap",
                 capacity: int = 512,
                 mesh=None,
                 item_spec=None,
                 max_pop: Optional[int] = None,
                 elastic: bool = True,
                 backend=None):
        self.policy = policy or StealPolicy(proportion=0.5,
                                            low_watermark=1,
                                            high_watermark=8,
                                            max_steal=min(256, capacity))
        self.execution = execution
        self.item_spec = _SPEC if item_spec is None else item_spec
        extra = {} if max_pop is None else {"max_pop": max_pop}
        self.runtime = launch_runtime(
            n_replicas, capacity, self.item_spec, execution=execution,
            mesh=mesh, policy=self.policy, adaptive=adaptive,
            adaptive_config=adaptive_config,
            fault_plan=FaultPlan() if elastic else None, backend=backend,
            **extra)
        self.replicas = [DeviceReplicaLane(self, i)
                         for i in range(n_replicas)]
        self._requests: Dict[int, object] = {}
        self.stolen = 0
        # Automatic failure detection (attach_detector): None = off.
        # Deliberately SEPARATE from any runtime-level detector — this
        # one is fed wall-clock wave observations by the cluster, not
        # the replayed fault schedule.
        self.detector = None

    # -- request table -------------------------------------------------------

    def lookup(self, rid: int):
        return self._requests[rid]

    # -- the AdmissionMaster surface ----------------------------------------

    @property
    def telemetry(self):
        """The runtime's unified round + wave stream (the cluster appends
        ``WaveRecord``s here, next to real executor ``RoundRecord``s)."""
        return self.runtime.telemetry

    @property
    def controller(self):
        return self.runtime.controller

    @property
    def rounds(self) -> int:
        return self.runtime.rounds_run

    @property
    def proportion(self) -> float:
        return self.runtime.proportion

    def submit(self, requests: Sequence) -> int:
        """Bulk-admit to the least-loaded live replica: ONE ring splice
        of the request-id batch (constant latency in the batch size)."""
        requests = list(requests)
        if not requests:
            return -1
        live = [r for r in self.replicas if not r.evicted]
        if not live:
            raise RuntimeError("every replica is evicted; nothing can admit")
        target = min(live, key=lambda r: r.load())
        for r in requests:
            self._requests[r.rid] = r
        rids = jnp.asarray([r.rid for r in requests], jnp.int32)
        pushed = self.runtime.push(target.replica_id, rids, len(requests))
        if pushed < len(requests):
            raise RuntimeError(
                f"admission ring overflow on replica {target.replica_id}: "
                f"pushed {pushed}/{len(requests)} (capacity "
                f"{self.runtime.capacity})")
        return target.replica_id

    # -- planned eviction ----------------------------------------------------

    def evict(self, replica_id: int) -> int:
        """Planned eviction on device: kill the lane in the runtime's
        fault schedule, then run recovery rounds until its ring is empty
        — each round is the ordinary exchange superstep executing the
        proportion-1.0 dead-worker plan, so the drain costs zero new
        kernels.  Returns the number of queued requests drained off the
        lane.  Requires ``elastic=True`` (the default)."""
        from repro.distributed.elastic import evacuate

        lane = self.replicas[replica_id]
        drained = int(len(lane))
        evacuate(self.runtime, [replica_id])
        lane.evicted = True
        self.telemetry.record_fault("evict")
        return drained

    def readmit(self, replica_id: int) -> None:
        """Re-admit an evicted lane: revive it in the fault schedule so
        the next plans may route work back into it.  Detector state and
        straggler penalty for the lane clear (``revive_lane`` clears the
        runtime controller's attribution; the master's own detector is
        revived here)."""
        self.runtime.revive_lane(replica_id)
        if self.detector is not None:
            self.detector.revive(replica_id)
        self.replicas[replica_id].evicted = False
        self.telemetry.record_fault("readmit")

    def note_straggler(self, rounds: int = 4, factor: float = 1.5,
                       lane: Optional[int] = None) -> None:
        """A replica was flagged slow: delegates to the runtime (counter
        + temporary steal-proportion boost, attributed to ``lane``)."""
        self.runtime.note_straggler(rounds=rounds, factor=factor, lane=lane)

    def attach_detector(self, policy=None):
        """Arm the shared :class:`repro.runtime.detector.FailureDetector`
        escalation policy: SUSPECTED -> straggler boost, DEAD -> real
        on-device :meth:`evict` (lane killed, ring drained by recovery
        supersteps; recorded as ``auto_evict``).  The owner feeds
        observations; :meth:`readmit` revives.  Returns the detector."""
        from repro.runtime.detector import DetectorPolicy, FailureDetector

        pol = policy or DetectorPolicy()

        def on_suspect(rid: int) -> None:
            self.note_straggler(rounds=pol.boost_rounds,
                                factor=pol.boost_factor, lane=rid)

        def on_dead(rid: int) -> None:
            if not self.replicas[rid].evicted:
                self.evict(rid)
                self.telemetry.record_fault("auto_evict")

        def on_revive(rid: int) -> None:
            if self.controller is not None:
                self.controller.clear_straggler(rid)

        self.detector = FailureDetector(len(self.replicas), pol,
                                        on_suspect=on_suspect,
                                        on_dead=on_dead,
                                        on_revive=on_revive)
        return self.detector

    def rebalance(self) -> int:
        """One REAL rebalance round through the executor (plan + exchange
        + adaptive update + telemetry on device).  Returns requests
        moved."""
        before = self.runtime.telemetry.total_transferred
        self.runtime.round()
        moved = self.runtime.telemetry.total_transferred - before
        self.stolen += moved
        return moved

    def rebalance_many(self, k: int) -> int:
        """Up to ``k`` rounds per tick, stopping once a round moves
        nothing (the host master's early-exit discipline)."""
        moved = 0
        for _ in range(int(k)):
            step = self.rebalance()
            moved += step
            if step == 0:
                break
        return moved

    def stats(self) -> Dict:
        return {
            "loads": [r.load() for r in self.replicas],
            "queued": [len(r) for r in self.replicas],
            "completed": [r.completed for r in self.replicas],
            "evicted": [r.replica_id for r in self.replicas if r.evicted],
            "stolen": self.stolen,
            "rounds": self.rounds,
            "proportion": self.proportion,
            "execution": self.execution,
            "backend": self.runtime.ops.resolved,
            "telemetry": self.telemetry.summary(),
        }

    def metrics(self, registry=None):
        """Poll this master into a :class:`repro.obs.metrics.
        MetricsRegistry`: the admission surface (per-replica loads,
        steal totals, detector census) PLUS the backing runtime's lane
        metrics — one registry covers both layers of the device
        master."""
        from repro.obs.metrics import collect_runtime, master_metrics

        reg = master_metrics(self, registry)
        return collect_runtime(reg, self.runtime)
