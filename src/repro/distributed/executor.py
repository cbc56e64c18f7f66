"""Mesh-native executor: the FULL round loop under ``shard_map``.

:class:`MeshStealRuntime` is :class:`repro.runtime.StealRuntime` with the
execution mode swapped: instead of ``jax.vmap`` lanes on one device, the
whole round — worker body, compact/dense exchange, adaptive proportion
update, telemetry accumulation — compiles as ONE ``shard_map``-ped fused
block over a real mesh worker axis (or a 2-D ``(pod, worker)`` mesh for
hierarchical supersteps).  Each device owns exactly one queue lane:

* **Per-device queue shards** — the stacked :class:`~repro.core.ops.
  QueueState` is placed with a :class:`~jax.sharding.NamedSharding`
  over the lane axis at construction, so lane i's ring buffer lives on
  device i from the first byte and never moves; the fused block donates
  the whole stack, which under shard_map donates each device's shard in
  place (skipped on CPU like the vmapped runtime).
* **The round body is shared, not ported** — both runtimes build on
  :func:`repro.runtime.executor.make_lane_step`; under shard_map the
  superstep's collectives (size all_gather, window all_gather /
  all_to_all) resolve through the mesh axes instead of vmap axes and
  become real ICI/DCN traffic.  The parity suite asserts queues, stats
  and adaptive-proportion trajectories are bit-identical between modes.
* **Device-resident round loop** — ``run_fused(k)`` places the
  ``lax.scan`` (or the ``until_drained`` ``lax.while_loop``) INSIDE the
  shard_map block: k rounds of collectives + adaptive feedback run
  without the host in the loop, and the drain check is a replicated
  cross-shard reduction of the queue sizes and of any work in flight the
  carry reports (every device takes the same exit branch).
* **Exact cross-host telemetry** — each shard stacks its OWN lane's
  per-round ``RebalanceStats`` counters; one all-gather of them all at
  the block edge puts them back into the vmapped runtime's exact ``(k, W, ...)``
  lane layout, replicated and packed into the one buffer the host
  reads, so the one shared reduction
  (:func:`repro.runtime.telemetry.reduce_round_stats`) assembles the
  same exact :class:`~repro.runtime.telemetry.RoundRecord`s — including
  ``bytes_moved`` / ``bytes_moved_xpod`` — from per-shard counters.

Host-side surface (``push`` / ``drain`` / ``round`` / ``run_fused`` /
``run`` / telemetry / the adaptive controller) is inherited unchanged:
the mesh runtime overrides only how the round block is built.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import master as master_ops
from repro.core import ops as bulk_ops
from repro.runtime import resilience
from repro.runtime.adaptive import adaptive_update
from repro.runtime.executor import (FusedProgram, ReadbackLayout,
                                    StealRuntime, WorkerFn, in_flight,
                                    make_lane_step)

__all__ = ["MeshStealRuntime"]

_tmap = jax.tree_util.tree_map


def _strip_lane(tree):
    """Local shard ``(1, ...)`` -> per-lane view ``(...)``."""
    return _tmap(lambda x: x[0], tree)


def _add_lane(tree):
    """Per-lane view ``(...)`` -> local shard ``(1, ...)``."""
    return _tmap(lambda x: x[None], tree)


class MeshStealRuntime(StealRuntime):
    """Drives adaptive rebalancing rounds with one queue lane per device.

    Args:
      mesh: a 1-axis mesh (flat supersteps over its axis) or a 2-axis
        ``(pod_axis, worker_axis)`` mesh (hierarchical supersteps;
        ``pod_size`` is the worker-axis extent).  Build one with
        :func:`repro.launch.mesh.make_worker_mesh` — the default axis
        names match the vmapped runtime's, so worker bodies written for
        one mode run unmodified in the other.
      capacity / item_spec / policy / adaptive / adaptive_config /
      backend / max_pop / programs: exactly as
      :class:`~repro.runtime.StealRuntime`.
    """

    def __init__(self, mesh: Mesh, capacity: int, item_spec, **kwargs):
        axes = tuple(mesh.axis_names)
        if len(axes) == 1:
            pod_axis, worker_axis = None, axes[0]
            pod_size = None
        elif len(axes) == 2:
            pod_axis, worker_axis = axes
            pod_size = int(mesh.shape[worker_axis])
        else:
            raise ValueError(
                f"MeshStealRuntime wants a 1-axis (flat) or 2-axis "
                f"(pod, worker) mesh, got axes {axes}")
        for key in ("axis_name", "pod_axis", "pod_size", "n_workers",
                    "queue_sharding"):
            if key in kwargs:
                raise TypeError(
                    f"MeshStealRuntime derives {key!r} from the mesh; "
                    f"don't pass it")
        n_workers = int(np.prod([mesh.shape[a] for a in axes]))
        self.mesh = mesh
        # One PartitionSpec entry shards the leading lane dim over EVERY
        # mesh axis (pod-major, matching the stacked lane order); the
        # trailing ring dims stay replicated-within-the-shard, i.e. each
        # device holds its whole lane.
        self._lane_entry = axes if len(axes) > 1 else axes[0]
        self._lane_spec = P(self._lane_entry)
        self.sharding = NamedSharding(mesh, self._lane_spec)
        # The queue stack is BORN sharded (lane i's ring on device i from
        # the first byte) — never built dense and re-placed.
        super().__init__(n_workers, capacity, item_spec,
                         axis_name=worker_axis, pod_size=pod_size,
                         pod_axis=pod_axis or "pods",
                         queue_sharding=self.sharding, **kwargs)

    # -- host-level lane access ----------------------------------------------

    def lane(self, worker: int) -> bulk_ops.QueueState:
        """Lane ``worker``'s queue on its own device.  Sliced off the
        sharded stack it is replicated over the mesh, where a Pallas
        queue kernel cannot run (Mosaic kernels are not partitioned)."""
        return jax.device_put(super().lane(worker),
                              self.mesh.devices.flat[worker])

    def set_lane(self, worker: int, qi: bulk_ops.QueueState) -> None:
        super().set_lane(worker, jax.device_put(
            qi, NamedSharding(self.mesh, P())))

    # -- the round, shard_mapped --------------------------------------------

    def _replicated_sharding(self):
        return NamedSharding(self.mesh, P())

    def _axes_tuple(self) -> tuple:
        return tuple(self.mesh.axis_names)

    def _make_step(self, worker_fn: Optional[WorkerFn]) -> Callable:
        """Un-jitted ``(qs, carry, proportion, ctx) -> (qs, carry,
        stats)``, identical signature and output layout to the vmapped
        runtime's — but each lane executes on its own device and the
        stats come back gathered into the stacked ``(W, ...)`` lane
        order.  The fault context is replicated (the schedule is the
        virtual master's view, identical on every device)."""
        lane_fn = self._lane_step(worker_fn)
        lane = self._lane_spec
        ctx_spec = resilience.ctx_specs(self.fault is not None)

        def local_step(qs, carry, proportion, ctx):
            q, c = _strip_lane(qs), _strip_lane(carry)
            q, c, stats = lane_fn(q, c, proportion, ctx)
            return _add_lane(q), _add_lane(c), _add_lane(stats)

        return jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(lane, lane, P(), ctx_spec),
            out_specs=(lane, lane, lane),
            check_vma=False)

    def _fused_round(self, worker_fn: Optional[WorkerFn]) -> Callable:
        """Per-shard ``(q, carry, p) -> (q, carry, p', tele, total)``:
        one round plus the on-device adaptive update and the replicated
        global total of queued items and of the work in flight the carry
        reports (the drain signal).  ``tele`` leaves carry a
        leading local-lane dim so shard_map's out specs can gather them
        into the vmapped runtime's exact telemetry layout."""
        lane_fn = self._lane_step(worker_fn)
        policy, controller = self.policy, self.controller
        config = controller.config if controller else None
        worker_axis = self.axis_name
        pod_axis = self.pod_axis if self.pod_size is not None else None
        axes = self._axes_tuple()

        def one_round(q, carry, p, ctx):
            q, carry, stats = lane_fn(q, carry, p, ctx)
            # The master's bookkeeping, re-used twice: the TRUE global
            # size vector feeds the same float32 adaptive step the vmap
            # runtime scans (bit-identical trajectory), and its sum is
            # the replicated drain signal for the while_loop exit.
            sizes_vec = master_ops.gather_sizes(
                q, worker_axis=worker_axis, pod_axis=pod_axis)
            tele = {"stats": _add_lane(stats),
                    "sizes": q.size[None],
                    "proportion": p}
            ctx = resilience.ctx_advance(ctx)
            if controller is not None:
                # Identical dead-lane masking to the vmap fused path, so
                # faulted adaptive trajectories stay bit-identical too.
                masked = resilience.mask_sizes(sizes_vec, ctx, policy)
                p = adaptive_update(p, masked, policy=policy,
                                    config=config)
            total = jnp.sum(sizes_vec)
            held = in_flight(carry)
            if held is not None:
                total = total + lax.psum(held, axes)
            return q, carry, p, ctx, tele, total

        return one_round

    def _tele_slots(self, k: int):
        """Preallocated per-shard ``(k, ...)`` telemetry slots for the
        early-exit loop.  Shapes are written out (not eval_shape'd): the
        superstep's gather widths are static — intra-level stats gather
        over the worker axis (``pod_size`` wide, or W when flat), the
        hierarchical ``sizes_after`` over the pod axis."""
        W, pod = self.n_workers, self.pod_size
        before_w = pod if pod is not None else W
        after_w = (W // pod) if pod is not None else W
        i32 = lambda *s: jnp.zeros((k,) + s, jnp.int32)
        stats = master_ops.RebalanceStats(
            sizes_before=i32(1, before_w), sizes_after=i32(1, after_w),
            n_transferred=i32(1), n_steals=i32(1),
            n_transferred_xpod=i32(1), n_steals_xpod=i32(1),
            bytes_moved=i32(1), bytes_moved_xpod=i32(1))
        return {"stats": stats, "sizes": i32(1),
                "proportion": jnp.zeros((k,), jnp.float32)}

    def _compile_fused(self, worker_fn: Optional[WorkerFn], k: int,
                       until_drained: bool = False) -> Callable:
        """The whole k-round loop INSIDE one shard_map block: scan (or
        early-exit while_loop) over the shared round body, collectives
        and the adaptive carry never leaving the devices; telemetry
        stacked per shard, all-gathered once at the block edge into the
        vmapped runtime's ``(k, W, ...)`` layout and packed with the
        round count and the final proportion into one replicated buffer,
        so the host reads one shard once.  The final proportion and the
        advanced fault context come back replicated too, placed as the
        next dispatch takes them."""
        one_round = self._fused_round(worker_fn)
        lane, entry = self._lane_spec, self._lane_entry
        axes = self._axes_tuple()
        ctx_spec = resilience.ctx_specs(self.fault is not None)
        layout = ReadbackLayout()

        W = self.n_workers

        def gather_lanes(tree):
            """One all-gather for every per-lane ``(k, 1, ...)`` int32
            leaf, as one row a lane; returns them in the vmapped
            runtime's ``(k, W, ...)`` layout."""
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            rows = lax.all_gather(
                jnp.concatenate([x.reshape(-1) for x in leaves]), entry)
            out, at = [], 0
            for x in leaves:
                y = rows[:, at:at + x.size].reshape((W,) + x.shape)
                out.append(jnp.moveaxis(y, 0, 1).reshape(
                    x.shape[:1] + (W,) + x.shape[2:]))
                at += x.size
            return jax.tree_util.tree_unflatten(treedef, out)

        def local_fused(qs, carry, p0, ctx0):
            q, c = _strip_lane(qs), _strip_lane(carry)

            if not until_drained:
                def body(state, _):
                    q, c, p, ctx = state
                    q, c, p, ctx, tele, _total = one_round(q, c, p, ctx)
                    return (q, c, p, ctx), tele

                (q, c, p, ctx), tele = lax.scan(body, (q, c, p0, ctx0),
                                                None, length=k)
                rounds = jnp.int32(k)
            else:
                tele0 = self._tele_slots(k)

                def cond(state):
                    _q, _c, _p, _ctx, r, _tele, total = state
                    return (r < k) & (total > 0)

                def body(state):
                    q, c, p, ctx, r, tele, _ = state
                    q, c, p, ctx, t, total = one_round(q, c, p, ctx)
                    tele = _tmap(
                        lambda buf, v: lax.dynamic_update_index_in_dim(
                            buf, v, r, 0), tele, t)
                    return (q, c, p, ctx, r + 1, tele, total)

                # The replicated global drain signal: queued items, and
                # the work in flight the carry reports.
                held = in_flight(c)
                total0 = lax.psum(q.size if held is None else q.size + held,
                                  axes)
                q, c, p, ctx, rounds, tele, _ = lax.while_loop(
                    cond, body,
                    (q, c, p0, ctx0, jnp.int32(0), tele0, total0))

            tele = dict(gather_lanes({"stats": tele["stats"],
                                      "sizes": tele["sizes"]}),
                        proportion=tele["proportion"])
            return (_add_lane(q), _add_lane(c), p, ctx,
                    layout.pack(rounds, p, tele))

        fused = jax.shard_map(
            local_fused, mesh=self.mesh,
            in_specs=(lane, lane, P(), ctx_spec),
            out_specs=(lane, lane, P(), ctx_spec, P()),
            check_vma=False)
        return FusedProgram(fused, layout, self._donate_argnums(),
                            self._replicated_sharding())

    # -- resilience: elastic state shardings ---------------------------------

    def _state_shardings(self, template):
        """Elastic restore onto THIS mesh: queue lanes land sharded on
        their owning devices (``self.sharding``), everything else —
        proportion, round counter, fault schedule — replicated.  This is
        what lets a snapshot written under one topology (8-device mesh)
        restore onto another (1 device, or a reshaped mesh): the
        checkpoint holds full host arrays and placement is decided here,
        by the restoring runtime."""
        rep = NamedSharding(self.mesh, P())
        return {
            key: (_tmap(lambda _: self.sharding, template["queues"])
                  if key == "queues"
                  else _tmap(lambda _: rep, template[key]))
            for key in template
        }
