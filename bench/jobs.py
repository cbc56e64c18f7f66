"""The job drivers, found by the traffic's ``kind``.

A traffic file (``traffic/<name>.json``) names its ``kind`` and the
parameters of its job stream; a configuration file (``configs/<name>.
json``) gives the deployment the jobs run on.  The driver of a kind is
the class ``Driver`` in ``drivers/<kind>.py``, loaded by name as the
per-layer readers are, so a new kind of job is a new file.

A driver is built as ``Driver(config, traffic, layout, seed)`` and has
``setup()`` (build and warm up every program the window runs),
``window(seconds, clock, traced)`` (the measured loop; returns the
end-to-end numbers and the counters of the window), ``finish()``
(completes what the window left in flight, untimed), ``attempted()``,
``kernel_rows()`` (the queue kernels' kinds by output rows, for the
trace reduction) and ``checks()`` (the comparison with the plain
reference, as ``(name, value, limit)`` triples, and the count of failed
answers).  It runs the layout it is given: ``layout["execution"]`` is
``vmap`` (every lane on one chip) or ``mesh`` (one lane a chip).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, Tuple

Check = Tuple[str, float, float]

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_LOADED: Dict[str, object] = {}


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench:{name}")


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, from
    JAX's own ``backend_compile_duration`` monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        del duration, kwargs
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return False


def driver_path(kind: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "drivers", f"{kind}.py")


def load_driver(kind: str, bench_dir: str = BENCH_DIR):
    """The module ``drivers/<kind>.py`` (loaded once per path)."""
    path = driver_path(kind, bench_dir)
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise ValueError(f"no driver for traffic kind {kind!r} "
                             f"({path} does not exist)")
        spec = importlib.util.spec_from_file_location(
            f"bench_driver_{kind}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def make(config: dict, traffic: dict, layout: dict, seed: int,
         bench_dir: str = BENCH_DIR):
    return load_driver(traffic["kind"], bench_dir).Driver(
        config, traffic, layout, seed)
