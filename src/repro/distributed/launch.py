"""One entry point for "give me an executor": vmap lanes or a real mesh.

``launch_runtime`` is how consumers (the DD solver's ``parallel_solve``,
the serving cluster, the benchmarks) select the execution mode without
knowing either runtime class: ``execution="vmap"`` builds the
single-device lane simulation (:class:`repro.runtime.StealRuntime`),
``execution="mesh"`` builds the device-per-lane
:class:`~repro.distributed.executor.MeshStealRuntime` on a worker mesh
from :func:`repro.launch.mesh.make_worker_mesh` (or a mesh you pass in).
Both return the same object surface — ``push`` / ``round`` /
``run_fused`` / ``run`` / ``telemetry`` — with the same axis names, so
worker bodies and driving code are mode-agnostic.
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import Mesh

from repro.distributed.executor import MeshStealRuntime
from repro.launch.mesh import make_worker_mesh
from repro.runtime.executor import StealRuntime

__all__ = ["launch_runtime"]

EXECUTIONS = ("vmap", "mesh")


def launch_runtime(n_workers: int, capacity: int, item_spec, *,
                   execution: str = "mesh",
                   mesh: Optional[Mesh] = None,
                   pod_size: Optional[int] = None,
                   axis_name: str = "workers",
                   pod_axis: str = "pods",
                   **kwargs) -> StealRuntime:
    """Construct the executor for ``execution`` in ``("vmap", "mesh")``.

    ``pod_size`` selects hierarchical supersteps in either mode (a 2-D
    ``(pod, worker)`` mesh when ``execution="mesh"``).  ``mesh``
    optionally pins the mesh instead of building one over the first
    ``n_workers`` process devices; it must agree with ``n_workers`` /
    ``pod_size``.  Remaining keywords (``policy`` / ``adaptive`` /
    ``adaptive_config`` / ``backend`` / ``max_pop`` / ``fault_plan`` /
    ``programs``)
    pass through to the runtime unchanged.
    """
    if execution == "vmap":
        if mesh is not None:
            raise ValueError("execution='vmap' takes no mesh")
        return StealRuntime(n_workers, capacity, item_spec,
                            axis_name=axis_name, pod_size=pod_size,
                            pod_axis=pod_axis, **kwargs)
    if execution != "mesh":
        raise ValueError(
            f"unknown execution {execution!r}; expected one of {EXECUTIONS}")
    if mesh is None:
        mesh = make_worker_mesh(n_workers, pod_size=pod_size,
                                axis_name=axis_name, pod_axis=pod_axis)
    else:
        if int(mesh.devices.size) != n_workers:
            raise ValueError(
                f"mesh has {mesh.devices.size} devices but n_workers="
                f"{n_workers}")
        # A pinned mesh must agree with the requested hierarchy — a flat
        # mesh with pod_size (or vice versa) would silently run the
        # OTHER superstep mode.
        mesh_pod = (int(mesh.shape[mesh.axis_names[-1]])
                    if len(mesh.axis_names) == 2 else None)
        if pod_size != mesh_pod:
            raise ValueError(
                f"mesh implies pod_size={mesh_pod} (axes "
                f"{tuple(mesh.axis_names)}) but pod_size={pod_size} was "
                f"requested")
    return MeshStealRuntime(mesh, capacity, item_spec, **kwargs)
