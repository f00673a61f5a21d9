"""Three-term roofline from a compiled dry-run artifact.

  compute   = HLO_FLOPs / peak_FLOP/s            (per-chip: post-SPMD HLO
  memory    = HLO_bytes / HBM_bw                  is the per-device program)
  collective= collective_bytes / link_bw

collective_bytes is parsed from the optimized (post-partitioning) HLO text:
the summed operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops, per the assignment's definition.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro.roofline import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _type_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _shape_str_bytes(s: str) -> int:
    """Bytes of a result type string, incl. tuple types '(f32[2], f32[2])'."""
    return sum(_type_bytes(d, dims) for d, dims in _TYPE_RE.findall(s))


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes per collective kind from optimized HLO text.

    Optimized HLO prints operands as bare %names, so first build a symbol
    table name -> result-type bytes, then resolve each collective's operand
    list against it.
    """
    sizes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        dm = _DEF_RE.match(line)
        if dm:
            sizes[dm.group(1)] = _shape_str_bytes(dm.group(2))

    out: dict[str, int] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:        # async pair: the -start carries operands
            continue
        kind = m.group(1)
        start = line.index(m.group(0)) + len(m.group(0))
        depth = 1
        i = start
        while i < len(line) and depth > 0:
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
            i += 1
        operands = line[start: i - 1]
        # inline-typed operands (unoptimized HLO) or bare names (optimized)
        b = sum(_type_bytes(d, s) for d, s in _TYPE_RE.findall(operands))
        if b == 0:
            b = sum(sizes.get(nm, 0) for nm in _OPERAND_RE.findall(operands))
        out[kind] = out.get(kind, 0) + b
        count[kind] = count.get(kind, 0) + 1
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["op_counts"] = count
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device bytes accessed (conservative)
    coll_bytes: float             # per-device collective operand bytes
    compute_s: float
    memory_s: float               # conservative (op-boundary) bound
    memory_fused_s: float         # optimistic (fusion-granularity) bound
    collective_s: float
    bottleneck: str
    model_flops_total: float      # 6ND (train) / 2ND (inference), global
    useful_flops_ratio: float     # model_flops_per_device / HLO flops
    step_s_bound: float           # max of the three terms
    mfu_bound: float              # model flops / (chips * peak * step_s_bound)

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline(flops: float, hbm_bytes: float, coll_bytes: float,
             n_chips: int, model_flops_total: float,
             links: int = 1, hbm_bytes_fused: float = None) -> RooflineTerms:
    """Roofline terms of one compiled program on ``n_chips`` chips of the
    dry-run's production target, the v5e."""
    c = hw.chip(hw.V5E)
    compute_s = flops / c.peak_flops_bf16
    memory_s = hbm_bytes / c.hbm_bw
    fused = hbm_bytes if hbm_bytes_fused is None else hbm_bytes_fused
    memory_fused_s = fused / c.hbm_bw
    collective_s = coll_bytes / (c.ici_bw_per_link * links)
    # bottleneck / MFU use the fused (TPU-fusion-granularity) memory bound;
    # the conservative bound is reported alongside.
    terms = {"compute": compute_s, "memory": memory_fused_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    step = max(compute_s, memory_fused_s, collective_s)
    mfu = (model_flops_total / (n_chips * c.peak_flops_bf16 * step)
           if step > 0 else 0.0)
    per_dev_model = model_flops_total / n_chips
    return RooflineTerms(
        flops=flops, hbm_bytes=hbm_bytes, coll_bytes=coll_bytes,
        compute_s=compute_s, memory_s=memory_s,
        memory_fused_s=memory_fused_s, collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops_total=model_flops_total,
        useful_flops_ratio=per_dev_model / flops if flops else 0.0,
        step_s_bound=step, mfu_bound=mfu,
    )


def model_flops(kind: str, n_params_active: int, tokens: int,
                embed_params: int = 0) -> float:
    """6ND for train, 2ND per forward token for prefill/decode.
    n_params excludes embedding table lookups (pass separately if desired)."""
    n = n_params_active - embed_params
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def cost_analysis_terms(compiled) -> tuple[float, float]:
    """(flops, bytes accessed) from compiled.cost_analysis(), tolerant of
    backend differences."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0)))
    return flops, byts
