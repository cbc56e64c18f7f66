import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves (a) the sharding config is coherent (GSPMD
partitions without error), (b) the per-device program fits HBM
(memory_analysis), and (c) yields the roofline terms (cost_analysis +
collective-bytes parsing) recorded in EXPERIMENTS.md.

The XLA_FLAGS line above MUST run before any jax import — jax locks the
device count on first init.  Only this entrypoint forces 512 host
devices; smoke tests and benchmarks see the real device count.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze_compiled
from repro.models import build_model
from repro.models.zoo import input_specs
from repro.train.optimizer import AdamWConfig, adamw_init, opt_state_specs
from repro.train.trainer import make_train_step

Pytree = Any


def _ns(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def model_flops_for(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train / 2*N*D inference (N_active for
    MoE; D = tokens processed)."""
    n = cfg.param_count()
    if cfg.n_experts:
        expert_p = 3 * cfg.d_model * cfg.d_ff_expert
        n -= cfg.n_layers * (cfg.n_experts - cfg.top_k) * expert_p
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, parallel):
    """Build + lower + compile one cell; returns (compiled, lowered)."""
    model = build_model(cfg, parallel)
    batch_sds, batch_ps = input_specs(cfg, shape, parallel)
    pspecs = model.param_specs()
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_ns = _ns(mesh, pspecs)
    batch_ns = _ns(mesh, batch_ps)

    if shape.kind == "train":
        mw = cfg.param_dtype == "bfloat16"  # master-weights mixed precision
        opt_sds = jax.eval_shape(
            lambda p: adamw_init(p, master_weights=mw), params_sds)
        opt_ns = _ns(mesh, opt_state_specs(pspecs, master_weights=mw))
        step = make_train_step(model, AdamWConfig(master_weights=mw))
        jitted = jax.jit(step,
                         in_shardings=(param_ns, opt_ns, batch_ns),
                         out_shardings=(param_ns, opt_ns, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_sds, opt_sds, batch_sds)

    elif shape.kind == "prefill":
        cache_ns = _ns(mesh, model.cache_specs(shape.seq_len,
                                               shape.global_batch))

        if cfg.family == "encdec":
            def step(params, batch):
                return model.prefill(params, batch["frames"], batch["tokens"])
        elif cfg.family == "vlm":
            def step(params, batch):
                return model.prefill(params, batch["tokens"], batch["patches"])
        else:
            def step(params, batch):
                return model.prefill(params, batch["tokens"])

        jitted = jax.jit(step,
                         in_shardings=(param_ns, batch_ns),
                         out_shardings=(None, cache_ns))
        lowered = jitted.lower(params_sds, batch_sds)

    else:  # decode
        B = shape.global_batch
        if cfg.family == "encdec":
            cache_sds = jax.eval_shape(
                lambda: model.make_cache(B, shape.seq_len, shape.seq_len))
        else:
            cache_sds = jax.eval_shape(
                lambda: model.make_cache(B, shape.seq_len))
        cache_ns = _ns(mesh, model.cache_specs(shape.seq_len, B))

        def step(params, cache, tokens):
            return model.decode_step(params, cache, tokens)

        jitted = jax.jit(step,
                         in_shardings=(param_ns, cache_ns,
                                       batch_ns["tokens"]),
                         out_shardings=(None, cache_ns),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_sds, cache_sds,
                               batch_sds["tokens"])

    compiled = lowered.compile()
    return compiled, lowered


def _cost_points(cfg: ModelConfig):
    """Small layer counts for the two/three-point cost extrapolation.

    Returns (points, combine) where ``combine(costs_by_L) -> scale dict``
    reconstructs the full-depth cost from the small unrolled variants:
    costs are linear in the layer count for homogeneous stacks, so
    f(L) = base + L_units * per_unit.
    """
    import dataclasses as dc

    L = cfg.n_layers
    if cfg.family == "hybrid" and cfg.attn_every:
        g = cfg.attn_every
        n_groups = L // g
        tail = L - n_groups * g
        pts = [g, 2 * g] + ([g + tail] if tail else [])

        def combine(f):
            per_group = _sub(f[2 * g], f[g])
            base = _sub(f[g], per_group)
            total = _add(base, _mul(per_group, n_groups))
            if tail:
                per_tail = _sub(f[g + tail], f[g])
                total = _add(total, per_tail)
            return total

        def make(n):
            return dc.replace(cfg, n_layers=n)
        return pts, combine, make

    group = 2 if cfg.local_global_every else 1
    pts = [group * 1, group * 2] if group > 1 else [2, 4]

    def combine(f):
        span = pts[1] - pts[0]
        per_layer = _mul(_sub(f[pts[1]], f[pts[0]]), 1.0 / span)
        base = _sub(f[pts[0]], _mul(per_layer, pts[0]))
        return _add(base, _mul(per_layer, L))

    def make(n):
        import dataclasses as dc
        if cfg.family == "encdec":
            return dc.replace(cfg, n_layers=n, n_encoder_layers=n)
        return dc.replace(cfg, n_layers=n)

    if cfg.family == "encdec":
        # enc and dec scale together: f(s) = base + s*(enc+dec); full has
        # Le = Ld = L so the same linear fit applies.
        pass
    return pts, combine, make


def _cost_dict(compiled, hlo, n_devices):
    from repro.launch.roofline import collective_bytes, normalize_cost_analysis

    ca = normalize_cost_analysis(compiled.cost_analysis())

    d = {"flops": float(ca.get("flops", 0.0)),
         "bytes": float(ca.get("bytes accessed", 0.0))}
    d.update({f"coll:{k}": v
              for k, v in collective_bytes(hlo, n_devices).items()})
    return d


def _sub(a, b):
    return {k: a[k] - b.get(k, 0.0) for k in a}


def _add(a, b):
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in set(a) | set(b)}


def _mul(a, s):
    return {k: v * s for k, v in a.items()}


def extrapolated_costs(cfg: ModelConfig, shape, mesh, parallel,
                       n_devices: int) -> Dict[str, float]:
    """Exact-by-linearity cost accounting: compile small FULLY-UNROLLED
    variants (inner scans — MoE chunks, KV blocks, loss chunks — unroll
    too) and extrapolate to the full depth.  Bounds every cost compile to
    a few layers instead of unrolling 64-81 layer stacks."""
    from repro.models import layers as layers_mod

    pts, combine, make = _cost_points(cfg)
    f = {}
    try:
        layers_mod.set_scan_unroll(True)
        for n in pts:
            small = make(n)
            compiled, _ = lower_cell(small, shape, mesh, parallel)
            f[n] = _cost_dict(compiled, compiled.as_text(), n_devices)
    finally:
        layers_mod.set_scan_unroll(False)
    total = combine(f)
    return {k: max(v, 0.0) for k, v in total.items()}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, unroll_costs: bool = True,
             variant: Dict[str, Any] | None = None) -> Dict[str, Any]:
    import dataclasses as _dc

    from repro.models import layers as layers_mod
    from repro.launch import roofline as rf

    cfg = configs.get(arch)
    if variant:
        cfg = _dc.replace(cfg, **variant)
    shape = next(s for s in configs.SHAPES if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_devices = mesh.size
    parallel = ParallelConfig(pod_axis="pod" if multi_pod else None)

    t0 = time.time()
    with jax.set_mesh(mesh):
        # Pass 1 — production (scanned) program: proves sharding coherence
        # and gives the honest memory_analysis.
        layers_mod.set_scan_unroll(False)
        compiled, lowered = lower_cell(cfg, shape, mesh, parallel)
        ma = compiled.memory_analysis()
        # Pass 2 — cost accounting via small unrolled variants: XLA's
        # cost_analysis counts while-loop bodies ONCE, so the scanned
        # program undercounts FLOPs/bytes/collectives ~n_layers-fold;
        # fully unrolling the assigned depths is compile-prohibitive, so
        # costs are extrapolated linearly in depth (exact for the
        # homogeneous stacks used here).
        if unroll_costs:
            costs = extrapolated_costs(cfg, shape, mesh, parallel, n_devices)
        else:
            costs = _cost_dict(compiled, compiled.as_text(), n_devices)

        flops = costs["flops"]
        byts = costs["bytes"]
        coll_total = costs.get("coll:total", 0.0)
        mf = model_flops_for(cfg, shape)
        compute_s = flops / rf.PEAK_FLOPS
        memory_s = byts / rf.HBM_BW
        collective_s = coll_total / rf.ICI_BW
        bottleneck = max([("compute", compute_s), ("memory", memory_s),
                          ("collective", collective_s)],
                         key=lambda kv: kv[1])[0]
    dt = time.time() - t0

    peak = (int(ma.argument_size_in_bytes) + int(ma.output_size_in_bytes)
            + int(ma.temp_size_in_bytes) - int(ma.alias_size_in_bytes))
    result = {
        "arch": arch, "shape": shape.name, "mesh": mesh_name,
        "status": "ok", "compile_s": round(dt, 1),
        "memory_analysis": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": peak,
            "fits_16g": peak <= 16 * 2 ** 30,
        },
        "cost_analysis": {
            "flops_per_device": flops,
            "bytes_per_device": byts,
        },
        "collectives": {k[5:]: v for k, v in costs.items()
                        if k.startswith("coll:") and k != "coll:total"},
        "collective_bytes_per_device": coll_total,
        "roofline": {"compute_s": compute_s, "memory_s": memory_s,
                     "collective_s": collective_s},
        "bottleneck": bottleneck,
        "model_flops": mf,
        "useful_ratio": (mf / (flops * n_devices)) if flops else 0.0,
    }
    if verbose:
        print(f"[{arch} x {shape.name} x {mesh_name}] compile={dt:.1f}s "
              f"mem(arg/temp/out)={ma.argument_size_in_bytes/2**30:.2f}/"
              f"{ma.temp_size_in_bytes/2**30:.2f}/"
              f"{ma.output_size_in_bytes/2**30:.2f} GiB  "
              f"terms(c/m/x)={compute_s*1e3:.2f}/{memory_s*1e3:.2f}/"
              f"{collective_s*1e3:.2f} ms  bottleneck={bottleneck}",
              flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--no-unroll", action="store_true",
                    help="skip the unrolled cost pass (faster, undercounts)")
    ap.add_argument("--moe-impl", default=None,
                    choices=["gspmd", "ep_shardmap"],
                    help="override MoE dispatch impl (perf variant)")
    ap.add_argument("--param-dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="override param dtype (bf16 => master weights)")
    ap.add_argument("--moe-bulk-steal", default=None, choices=["on", "off"],
                    help="override the bulk-steal rebalancing (ablation)")
    args = ap.parse_args()

    variant: Dict[str, Any] = {}
    if args.moe_impl:
        variant["moe_impl"] = args.moe_impl
    if args.param_dtype:
        variant["param_dtype"] = args.param_dtype
    if args.moe_bulk_steal:
        variant["moe_bulk_steal"] = args.moe_bulk_steal == "on"

    archs = list(configs.ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    failures = 0

    def _flush():
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    for arch in archs:
        cfg = configs.get(arch)
        cells = configs.cells_for(cfg)
        shapes = ([s.name for s in cells] if args.shape == "all"
                  else [args.shape])
        for shape_name in shapes:
            if shape_name not in [s.name for s in cells]:
                print(f"[{arch} x {shape_name}] SKIP (inapplicable; see "
                      f"DESIGN.md long_500k rule)")
                continue
            for mp in meshes:
                # The roofline table (§Roofline) is single-pod only, so the
                # expensive unrolled cost pass runs only there; multi-pod
                # cells prove sharding coherence + memory fit.
                unroll = (not args.no_unroll) and not mp
                try:
                    results.append(run_cell(arch, shape_name, mp,
                                            unroll_costs=unroll,
                                            variant=variant or None))
                except Exception as e:  # record the failure, keep sweeping
                    failures += 1
                    traceback.print_exc()
                    results.append({
                        "arch": arch, "shape": shape_name,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                    })
                _flush()
    _flush()
    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n== dry-run complete: {ok} ok / {failures} failed "
          f"-> {args.out} ==")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
