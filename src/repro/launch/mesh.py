"""Production mesh construction.  A FUNCTION (not a module-level constant)
so importing this module never touches jax device state."""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_worker_mesh", "CHIPS_PER_POD"]

CHIPS_PER_POD = 256  # 16 x 16 TPU v5e pod


def make_worker_mesh(n_workers: int, *, pod_size: int | None = None,
                     axis_name: str = "workers", pod_axis: str = "pods"):
    """A queue-worker mesh for the distributed executor: one device per
    queue lane along ``axis_name`` (flat), or a 2-D ``(pod_axis,
    axis_name)`` mesh of ``n_workers // pod_size`` pods when
    ``pod_size`` is set (hierarchical supersteps: cheap ICI within a
    pod, one representative block across pods).  The axis names default
    to the executors' defaults so a
    :class:`~repro.distributed.MeshStealRuntime` built on this mesh is
    collective-compatible with the vmapped :class:`~repro.runtime.
    StealRuntime` worker bodies (same names resolve either way).

    Uses the first ``n_workers`` process devices (like
    :func:`make_production_mesh`, oversubscribed hosts just leave the
    tail idle); raises when the process exposes fewer.
    """
    import numpy as np

    devices = jax.devices()
    if len(devices) < n_workers:
        raise ValueError(
            f"make_worker_mesh(n_workers={n_workers}) needs at least that "
            f"many devices; this process has {len(devices)} (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_workers} "
            f"before jax initializes to fake them on CPU)")
    if pod_size is None:
        return jax.sharding.Mesh(np.asarray(devices[:n_workers]),
                                 (axis_name,))
    if n_workers % pod_size != 0:
        raise ValueError(
            f"n_workers={n_workers} not divisible by pod_size={pod_size}")
    shape = (n_workers // pod_size, pod_size)
    return jax.sharding.Mesh(
        np.asarray(devices[:n_workers]).reshape(shape),
        (pod_axis, axis_name))


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single pod; (pod=2, data=16, model=16) two pods.

    When the process exposes more host devices than the mesh needs (the
    dry-run forces 512), the single-pod mesh uses the first 256.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) > n:
        import numpy as np

        return jax.sharding.Mesh(
            np.asarray(devices[:n]).reshape(shape), axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
