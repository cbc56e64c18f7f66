"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names its configuration (``configs/<config>.json``, the file the
configuration entry lists) and its traffic (``traffic/<traffic>.json``);
each metric is read by ``layer_metrics/<metric>.py``; the traffic's
``kind`` names its driver, ``drivers/<kind>.py``.  A run:

1. checks that JAX sees TPUs, as many as the cell asks for;
2. builds the job driver of the traffic's ``kind`` (``drivers/<kind>.py``,
   found by ``jobs.py``) and warms up every program its window runs
   (this is ``setup_s``);
3. runs the window for ``--seconds``; with ``--trace 1`` the window is
   traced by the profiler (capped at the traffic's ``trace_seconds``);
4. reads the peak device memory, completes what the window left in
   flight, and compares every answer with the plain reference;
5. prints the compared numbers with their limits as the last lines of
   standard error, and one JSON line as the last line of standard output.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------


def load_spec(path: str = SPEC_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration, its traffic and its metrics, by name."""
    cell = _by_name(spec["workloads"], workload, "workload")
    config_entry = _by_name(spec["configs"], cell["config"], "config")
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
    layout = config["layout"][str(cell["chips"])]
    check_layout(layout, int(cell["chips"]))

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "layout": layout,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def check_layout(layout: dict, chips: int) -> None:
    """A layout runs its lanes vmapped on one chip, or one lane a chip;
    any other is refused rather than run on fewer chips than reported."""
    execution, lanes = layout["execution"], int(layout["lanes"])
    if not ((execution == "vmap" and chips == 1)
            or (execution == "mesh" and lanes == chips)):
        raise ValueError(f"layout {layout} cannot run on {chips} chips: "
                         f"'vmap' needs 1 chip, 'mesh' one lane a chip")


def load_reader(name: str, root: str = ROOT) -> Callable[[dict], Optional[float]]:
    """``layer_metrics/<name>.py``'s ``read``."""
    path = os.path.join(root, "bench", "layer_metrics", f"{name}.py")
    module_spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX's first device is "
                            f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX sees "
                            f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_cache() -> str:
    """JAX's persistent compilation cache in the checkout's fixed
    ``.jax_cache/`` (or where ``JAX_COMPILATION_CACHE_DIR`` says), with
    every program kept, so that only a cell's first run compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    path = use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# The traced window
# ---------------------------------------------------------------------------


class Tracer:
    """A profiler session around the window; the trace goes to a
    temporary directory that is removed once it has been reduced."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def __enter__(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            jax.profiler.stop_trace()
        return False

    def reduce(self, chips: int, kernel_rows: Dict[int, str]
               ) -> Optional[dict]:
        if not self.on:
            return None
        from bench import trace_reduce

        try:
            (path,) = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                             "*", "*.xplane.pb"))
            raw = trace_reduce.extract(path, kernel_rows)
            return trace_reduce.reduce(raw, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: Optional[float] = None,
             overrides: Optional[Dict[str, dict]] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``overrides`` replaces keys of the configuration or the traffic (the
    tests run the harness at a size a CPU holds)."""
    t_start = time.perf_counter() if t_start is None else t_start
    found = resolve(load_spec(), workload)
    for part, values in (overrides or {}).items():
        found[part] = {**found[part], **values}
    chips = int(found["cell"]["chips"])
    clock = time.perf_counter
    device = device_info(chips, require_tpu)
    if require_tpu:
        use_cache()
    t_device = clock()

    from bench import jobs, peaks

    job = jobs.make(found["config"], found["traffic"], found["layout"], seed)
    with jobs.CompileCounter() as setup_compiles:
        job.setup()
    compiles = jobs.CompileCounter()
    window = seconds
    if trace:
        window = min(seconds, float(found["traffic"]["trace_seconds"]))
    setup_s = clock() - t_start
    with compiles, Tracer(trace) as tracer:
        measured = job.window(window, clock, traced=trace)
    device["memory_peak_bytes"] = memory_peak(chips)
    reduced = tracer.reduce(chips, job.kernel_rows())
    job.finish()
    checks, failed = job.checks()
    correct = all(value <= limit for _, value, limit in checks)

    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(measured["e2e"], setup_s=setup_s)
        for m in found["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = {"trace": reduced, "counters": measured["counters"],
               "peaks": peaks.lookup(device["kind"]) if require_tpu else None,
               "chips": chips}
        for m in found["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {"correct": correct, "attempted": job.attempted(),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        from bench import trace_reduce

        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["window_counts"] = dict(measured["counters"],
                                   elapsed_s=measured["elapsed_s"],
                                   compiles_in_window=compiles.count)
    # Where set-up goes: to the chip's first use (imports, finding the
    # devices), then building and warming up the job.
    result["setup_parts"] = {"to_device_s": t_device - t_start,
                             "job_s": setup_s - (t_device - t_start),
                             "compiles": setup_compiles.count}
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, check in result["checks"].items():
        print(f"check {name} = {check['value']} (limit {check['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
