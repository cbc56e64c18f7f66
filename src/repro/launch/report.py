"""Generate the EXPERIMENTS.md §Dry-run and §Roofline sections from the
dry-run JSON.

  PYTHONPATH=src python -m repro.launch.report results/dryrun.json
"""

from __future__ import annotations

import json
import sys
from typing import List

GiB = 2 ** 30


def _f(x, nd=1):
    return f"{x:.{nd}f}"


def dryrun_section(results: List[dict]) -> str:
    lines = [
        "## §Dry-run",
        "",
        "Every (arch × shape × mesh) cell `.lower().compile()`d with",
        "ShapeDtypeStruct inputs (no allocation). `mem/dev` is",
        "`memory_analysis()` peak per device (arguments + temps + outputs −",
        "aliased); the fit budget is TPU v5e's 16 GiB HBM. Collective",
        "volumes are per-device wire bytes (ring formulas over the parsed",
        "optimized HLO; table in §Roofline). Multi-pod cells prove the",
        '"pod" axis shards (DP across pods, params replicated per pod,',
        "grads all-reduced over DCN once per step).",
        "",
        "| arch | shape | mesh | compile s | args GiB | temps GiB | out GiB | peak GiB | fits 16G |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        if r.get("status") != "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | ERROR: "
                f"{r.get('error', '?')[:60]} | | | | | |")
            continue
        ma = r["memory_analysis"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{r['compile_s']} | {_f(ma['argument_bytes']/GiB, 2)} | "
            f"{_f(ma['temp_bytes']/GiB, 2)} | "
            f"{_f(ma['output_bytes']/GiB, 2)} | "
            f"{_f(ma['peak_bytes']/GiB, 2)} | "
            f"{'Y' if ma.get('fits_16g') else 'N'} |")
    ok = sum(1 for r in results if r.get("status") == "ok")
    lines += ["", f"**{ok}/{len(results)} cells compile.**", ""]
    return "\n".join(lines)


def roofline_section(results: List[dict]) -> str:
    lines = [
        "## §Roofline",
        "",
        "Single-pod (16×16 = 256 chips) per-device terms, from a fully",
        "UNROLLED second lowering of each cell (XLA's `cost_analysis()`",
        "counts `while` bodies once, so the scanned program would",
        "undercount ~n_layers-fold — see launch/dryrun.py).",
        "",
        "- compute = HLO_FLOPs/dev ÷ 197 TF/s · memory = HLO_bytes/dev ÷",
        "  819 GB/s · collective = wire_bytes/dev ÷ 50 GB/s/link.",
        "- `useful` = MODEL_FLOPS (6·N·D train / 2·N·D inference,",
        "  N_active for MoE) ÷ (HLO_FLOPs × 256). The gap is attention",
        "  quadratics, remat recompute, and the blocked-attention 2×",
        "  causal waste.",
        "- CAVEAT: HLO_bytes comes from the CPU-backend cost model, which",
        "  reflects much weaker fusion than TPU codegen — treat the memory",
        "  term as an unfused UPPER bound and a relative metric between",
        "  variants, not a TPU wall-clock prediction.",
        "",
        "| arch | shape | compute ms | memory ms | collective ms | bottleneck | useful | ag/ar/rs/a2a/cp MB |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        if r.get("status") != "ok" or r.get("mesh") != "16x16":
            continue
        rt = r["roofline"]
        cb = r.get("collectives", {})
        mb = "/".join(
            f"{cb.get(k, 0)/2**20:.0f}"
            for k in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute"))
        lines.append(
            f"| {r['arch']} | {r['shape']} | {_f(rt['compute_s']*1e3)} | "
            f"{_f(rt['memory_s']*1e3)} | {_f(rt['collective_s']*1e3)} | "
            f"{r['bottleneck']} | {_f(r['useful_ratio'], 3)} | {mb} |")
    lines.append("")
    return "\n".join(lines)


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "results/dryrun.json"
    with open(path) as f:
        results = json.load(f)
    print(dryrun_section(results))
    print()
    print(roofline_section(results))


if __name__ == "__main__":
    main()
