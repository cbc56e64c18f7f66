"""Roofline-term extraction from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips x peak FLOP/s)
    memory term     = HLO_bytes / (chips x HBM bw)
    collective term = collective wire bytes / (chips x link bw)

``cost_analysis()`` on a GSPMD-compiled executable reports the PER-DEVICE
program's flops/bytes, so the "/ chips" division is already implicit —
we document both conventions and report per-device terms directly.

Collective bytes are NOT in cost_analysis: we parse the optimized HLO
text and apply ring-algorithm wire formulas per op:

    all-gather(result R bytes, group g):     R * (g-1)/g         received
    reduce-scatter(operand O bytes, group g): O * (g-1)/g        sent
    all-reduce(operand O bytes, group g):    2 * O * (g-1)/g     (RS + AG)
    all-to-all(operand O bytes, group g):    O * (g-1)/g
    collective-permute(operand O bytes):     O

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (per direction).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["HW", "RooflineReport", "analyze_compiled", "collective_bytes",
           "normalize_cost_analysis"]

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

HW = {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW, "ici_bw": ICI_BW}

def normalize_cost_analysis(ca) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a plain dict: the installed jax
    returns one flat dict, or ``None`` where the backend has no cost
    model, so callers can ``.get("flops", 0)`` unconditionally."""
    return dict(ca or {})


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "e4m3": 1,
    "e5m2": 1, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  "f32[16,128]{1,0}"  or "bf16[2,4,8]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_ARR_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str.strip())
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    bpe = _DTYPE_BYTES.get(dt)
    if bpe is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * bpe


def _result_bytes(line: str) -> int:
    """Sum byte sizes of the op's result (handles tuple results)."""
    # result type is between '=' and the op name
    try:
        lhs, rhs = line.split(" = ", 1)
    except ValueError:
        return 0
    # rhs starts with the type, e.g. "f32[8,16]{1,0} all-gather(" or
    # "(f32[8], f32[8]) all-reduce("
    ty = rhs.split(")", 1)[0] + ")" if rhs.startswith("(") else rhs.split(" ", 1)[0]
    return sum(_shape_bytes(s.group(0)) for s in _SHAPE_RE.finditer(ty))


def _operand_bytes(line: str) -> int:
    """Sum byte sizes of the operands (typed operand list in parens)."""
    # operands appear as  opname(f32[..] %x, bf16[..] %y, ...)
    m = re.search(r"\w[\w-]*\(([^)]*)\)", line.split(" = ", 1)[-1])
    if not m:
        return 0
    return sum(_shape_bytes(s.group(0)) for s in _SHAPE_RE.finditer(m.group(1)))


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_ARR_RE.search(line)
    if m:  # replica_groups=[g,n] — iota form: n groups... format [num_groups, group_size]?
        a, b = int(m.group(1)), int(m.group(2))
        return b if b > 0 else default
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].lstrip("{")
        ids = [x for x in first.split(",") if x.strip() != ""]
        if ids:
            return len(ids)
    return default


def collective_bytes(hlo_text: str, n_devices: int) -> Dict[str, float]:
    """Estimated per-device wire bytes by collective kind (ring algorithm),
    for ONE execution of the program."""
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if " = " not in ls:
            continue
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start)?\(", ls):
                kind = k
                break
        if kind is None or ls.startswith("ROOT tuple") or f"{kind}-done" in ls:
            continue
        g = _group_size(ls, n_devices)
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-gather":
            out[kind] += _result_bytes(ls) * frac
        elif kind == "reduce-scatter":
            out[kind] += _operand_bytes(ls) * frac
        elif kind == "all-reduce":
            out[kind] += 2.0 * _operand_bytes(ls) * frac
        elif kind == "all-to-all":
            out[kind] += _operand_bytes(ls) * frac
        else:  # collective-permute
            out[kind] += _operand_bytes(ls)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_breakdown: Dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float            # 6*N*D analytic (global)
    useful_ratio: float           # model_flops / (flops_per_device * chips)
    peak_memory_bytes: int        # from memory_analysis
    argument_bytes: int
    output_bytes: int
    temp_bytes: int

    def terms(self) -> Dict[str, float]:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s}


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     n_devices: int, model_flops: float,
                     hlo_text: Optional[str] = None) -> RooflineReport:
    ca = normalize_cost_analysis(compiled.cost_analysis())
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text, n_devices)

    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll["total"] / ICI_BW
    bn = max(
        [("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)], key=lambda kv: kv[1])[0]

    ma = compiled.memory_analysis()
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=coll["total"],
        collective_breakdown={k: v for k, v in coll.items() if k != "total"},
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bn, model_flops=model_flops,
        useful_ratio=(model_flops / (flops * n_devices)) if flops else 0.0,
        peak_memory_bytes=int(getattr(ma, "temp_size_in_bytes", 0))
        + int(getattr(ma, "argument_size_in_bytes", 0))
        + int(getattr(ma, "output_size_in_bytes", 0))
        - int(getattr(ma, "alias_size_in_bytes", 0)),
        argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
    )
