"""Benchmark entrypoint: one table per paper figure + the roofline report.

  PYTHONPATH=src python -m benchmarks.run [--quick]
  PYTHONPATH=src python -m benchmarks.run --json [--tiny] [--out BENCH_PR2.json]
  PYTHONPATH=src python -m benchmarks.run --sweep-adaptive [--tiny] \
      [--out BENCH_PR3.json]
  PYTHONPATH=src python -m benchmarks.run --scaling [--tiny] \
      [--out BENCH_PR4.json]
  PYTHONPATH=src python -m benchmarks.run --mesh [--tiny] \
      [--out BENCH_PR5.json]
  PYTHONPATH=src python -m benchmarks.run --serve [--tiny] \
      [--out BENCH_PR8.json]
  PYTHONPATH=src python -m benchmarks.run --chaos [--tiny] \
      [--out BENCH_PR9.json]
  PYTHONPATH=src python -m benchmarks.run --check

``--json`` runs the figures that seed the repo's perf trajectory (Fig. 6
push latency incl. the backend sweep, Fig. 7 steal latency, the Fig. 9
device workload's fused-vs-per-round supersteps, and the Fig. 10
dense-vs-compact exchange columns) and writes the raw numbers to a JSON
file; ``--tiny`` shrinks repeats/sizes so the whole sweep fits a CPU CI
smoke job.  ``--sweep-adaptive`` runs the steal-proportion autotuning
sweep (AdaptiveConfig gain/clamp vs static proportions on the Fig. 9
DAG workload) and records the winner in BENCH_PR3.json.  ``--scaling``
runs the full Fig. 10 worker-count scaling sweep (W x max_steal x
{dense, compact}: wall per round + exchange payload) into
BENCH_PR4.json.  ``--mesh`` runs the Fig. 11 vmap-lane vs shard_map
executor comparison (W fake host devices are claimed BEFORE jax
initializes, so run it as its own process) into BENCH_PR5.json.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


def run_json(out: str, tiny: bool) -> int:
    import jax

    from benchmarks import fig6_push, fig7_steal, fig9_dag, fig10_scaling

    t0 = time.time()
    results = {
        "meta": {
            "bench": "BENCH_PR2",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "tiny": tiny,
        }
    }
    t6, d6 = fig6_push.run(tiny=tiny)
    t6.show()
    results["fig6_push"] = d6
    t7, d7 = fig7_steal.run(tiny=tiny)
    t7.show()
    results["fig7_steal"] = d7
    t9, d9 = fig9_dag.device_run(tiny=tiny)
    t9.show()
    results["fig9_device_fused"] = d9
    t10, d10 = fig10_scaling.run(tiny=tiny)
    t10.show()
    results["fig10_scaling"] = d10
    results["meta"]["wall_s"] = time.time() - t0
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[benchmarks] wrote {out} "
          f"(kernel push flatness {d6['kernel_flatness_1_to_1024']:.2f}x, "
          f"fused speedup {d9['fused_speedup']:.2f}x, "
          f"fig10 payload ratio==W {d10['payload_ratio_equals_w']}, "
          f"{results['meta']['wall_s']:.1f}s)")
    return 0


def run_scaling(out: str, tiny: bool) -> int:
    import jax

    from benchmarks import fig10_scaling

    t0 = time.time()
    table, data = fig10_scaling.run(tiny=tiny)
    table.show()
    results = {
        "meta": {
            "bench": "BENCH_PR4",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "tiny": tiny,
            "wall_s": time.time() - t0,
        },
        "fig10_scaling": data,
    }
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[benchmarks] wrote {out} "
          f"(payload ratio==W {data['payload_ratio_equals_w']}, "
          f"{results['meta']['wall_s']:.1f}s)")
    return 0


def run_mesh(out: str, tiny: bool) -> int:
    # Claim the fake host devices BEFORE anything imports jax (importing
    # benchmarks.fig11_mesh already pulls jax in, so the env var is set
    # here, inline) — the worker mesh needs one device per lane.  8/64
    # mirror max(fig11_mesh.TINY_WORKERS / WORKERS).
    import os

    n = 8 if tiny else 64
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()

    import jax

    from benchmarks import fig11_mesh

    t0 = time.time()
    table, data = fig11_mesh.run(tiny=tiny)
    table.show()
    results = {
        "meta": {
            "bench": "BENCH_PR5",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "tiny": tiny,
            "wall_s": time.time() - t0,
        },
        "fig11_mesh": data,
    }
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[benchmarks] wrote {out} "
          f"(mesh matches vmap: {data['mesh_matches_vmap']}, "
          f"{results['meta']['wall_s']:.1f}s)")
    return 0


def run_serve(out: str, tiny: bool) -> int:
    # Mesh parity cells need one fake host device per lane; claim them
    # inline BEFORE jax initializes (the run_mesh discipline).
    import os

    from benchmarks import serve_decode

    serve_decode.force_host_devices(max(serve_decode.WORKERS))

    import jax

    t0 = time.time()
    table, data = serve_decode.run(tiny=tiny)
    table.show()
    results = {
        "meta": {
            "bench": "BENCH_PR8",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "tiny": tiny,
            "repro_check": os.environ.get("REPRO_CHECK", ""),
            "wall_s": time.time() - t0,
        },
        "serve_decode": data,
    }
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[benchmarks] wrote {out} "
          f"(parity {data['parity']['modes']} ok, balanced beats rr: "
          f"{data['balanced_beats_rr']}, "
          f"{results['meta']['wall_s']:.1f}s)")
    return 0


def run_chaos(out: str, tiny: bool) -> int:
    import os

    import jax

    from benchmarks import chaos_recovery

    t0 = time.time()
    table, data = chaos_recovery.run(tiny=tiny)
    table.show()
    results = {
        "meta": {
            "bench": "BENCH_PR9",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "tiny": tiny,
            "repro_check": os.environ.get("REPRO_CHECK", ""),
            "wall_s": time.time() - t0,
        },
        "chaos_recovery": data,
    }
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    recompiles = data["live_resize"]["recompiles_during_resize"]
    print(f"[benchmarks] wrote {out} "
          f"(armed-idle overhead flat "
          f"{data['armed_overhead']['armed flat']['overhead']:.2f}x / 2x4 "
          f"{data['armed_overhead']['armed 2x4']['overhead']:.2f}x, "
          f"auto-kills {data['detector']['auto_kills']}, "
          f"resize recompiles {recompiles}, "
          f"{results['meta']['wall_s']:.1f}s)")
    return 0


def run_adaptive_sweep(out: str, tiny: bool) -> int:
    import jax

    from benchmarks import fig9_dag

    t0 = time.time()
    table, data = fig9_dag.adaptive_sweep(tiny=tiny)
    table.show()
    results = {
        "meta": {
            "bench": "BENCH_PR3",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "tiny": tiny,
            "wall_s": time.time() - t0,
        },
        "adaptive_sweep": data,
    }
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[benchmarks] wrote {out} (winner: {data['winner']}, "
          f"{results['meta']['wall_s']:.1f}s)")
    return 0


def run_check() -> int:
    """Tiny Fig. 9 smoke under the conservation sanitizer: the same
    device workload runs unchecked (baseline wall) and with REPRO_CHECK=1
    (every BulkOps call validated, superstep conservation callbacks on),
    asserts zero violations, and reports the sanitizer overhead."""
    import os

    from benchmarks import fig9_dag
    from repro.analysis import sanitize

    had = os.environ.pop("REPRO_CHECK", None)
    try:
        t0 = time.time()
        _, base = fig9_dag.device_run(tiny=True)
        plain_s = time.time() - t0

        os.environ["REPRO_CHECK"] = "1"
        sanitize.reset_violations()
        t0 = time.time()
        _, checked = fig9_dag.device_run(tiny=True)
        checked_s = time.time() - t0
        sanitize.assert_clean()
    finally:
        if had is not None:
            os.environ["REPRO_CHECK"] = had
        else:
            os.environ.pop("REPRO_CHECK", None)
    print(f"[benchmarks] --check: 0 violations "
          f"(fused speedup {base['fused_speedup']:.2f}x unchecked / "
          f"{checked['fused_speedup']:.2f}x checked; sanitizer overhead "
          f"{checked_s / max(plain_s, 1e-9):.1f}x wall, "
          f"{plain_s:.1f}s -> {checked_s:.1f}s)")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the DAG workload (slowest)")
    ap.add_argument("--json", action="store_true",
                    help="write machine-readable results to --out")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (implies --json)")
    ap.add_argument("--sweep-adaptive", action="store_true",
                    help="AdaptiveConfig gain/clamp vs static proportions "
                         "on the Fig. 9 DAG workload -> BENCH_PR3.json")
    ap.add_argument("--scaling", action="store_true",
                    help="Fig. 10 worker-count scaling sweep (dense vs "
                         "compact exchange) -> BENCH_PR4.json")
    ap.add_argument("--mesh", action="store_true",
                    help="Fig. 11 vmap-lane vs shard_map executor "
                         "comparison (claims fake host devices; run as "
                         "its own process) -> BENCH_PR5.json")
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching decode serving: parity gate "
                         "(host/vmap/mesh multisets) + steal-balanced vs "
                         "static round-robin sweep -> BENCH_PR8.json")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-recovery chaos sweep: armed-idle overhead, "
                         "seeded kill/delay/drop drains (flat and 2x4 "
                         "pods), detector delay->kill conversion, live "
                         "no-rebuild resize -> BENCH_PR9.json")
    ap.add_argument("--check", action="store_true",
                    help="tiny Fig. 9 smoke under the conservation "
                         "sanitizer (REPRO_CHECK=1); fails on any "
                         "invariant violation and reports the overhead")
    ap.add_argument("--out", default=None,
                    help="output path for --json / --sweep-adaptive / "
                         "--scaling")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if args.check:
        return run_check()
    if args.chaos:
        return run_chaos(args.out or "BENCH_PR9.json", args.tiny)
    if args.serve:
        return run_serve(args.out or "BENCH_PR8.json", args.tiny)
    if args.mesh:
        return run_mesh(args.out or "BENCH_PR5.json", args.tiny)
    if args.scaling:
        return run_scaling(args.out or "BENCH_PR4.json", args.tiny)
    if args.sweep_adaptive:
        return run_adaptive_sweep(args.out or "BENCH_PR3.json", args.tiny)
    if args.json or args.tiny:
        return run_json(args.out or "BENCH_PR2.json", args.tiny)

    from benchmarks import (fig6_push, fig7_steal, fig8_optimized_steal,
                            pop_parity, fig9_dag, fig10_scaling,
                            roofline_report, moe_steal, solver_scale)

    t0 = time.time()
    fig6_push.run()[0].show()
    fig7_steal.run()[0].show()
    fig8_table, fig8b_table, _, _ = fig8_optimized_steal.run()
    fig8_table.show()
    fig8b_table.show()
    pop_parity.run().show()
    moe_steal.run().show()
    solver_scale.run().show()
    fig9_dag.device_run()[0].show()
    fig10_scaling.run()[0].show()
    if not args.quick:
        fig9_dag.run().show()
    tb = roofline_report.run()
    if tb:
        tb.show()
    print(f"[benchmarks] total {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
