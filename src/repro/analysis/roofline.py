"""Roofline-term derivation from compiled XLA artifacts (no real hardware).

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.

The compiled module is the per-device SPMD program, so ``cost_analysis()``
FLOPs/bytes are per-device, and collective operand bytes parsed from the
post-partitioning HLO are per-device too.  Terms (seconds):

    compute    = flops_per_device / PEAK_FLOPS
    memory     = hbm_bytes_per_device / HBM_BW
    collective = collective_operand_bytes_per_device / ICI_BW
                 (== global_collective_bytes / (chips * ICI_BW))
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*((?:\([^=]*?\)|[\w\[\],{}\/ ]+?))\s+([\w\-]+)\("
)
_TYPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]"
)
_OPERAND_RE = re.compile(r"\((%[\w.\-]+(?:,\s*%[\w.\-]+)*)?\)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _types_bytes(type_str: str) -> int:
    return sum(_shape_bytes(t, d) for t, d in _TYPE_RE.findall(type_str))


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum OPERAND bytes of every collective op, per kind (per-device).

    Post-partitioning CPU HLO lists operands by name only, so this is a
    two-pass parse: 1) map op name -> result type, 2) resolve collective
    operand names.  ``-start`` async halves are counted; their ``-done``
    halves are not.  Collectives inside while bodies appear once — the
    dry-run's layer extrapolation recovers trip counts.
    """
    defs: Dict[str, str] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            defs[m.group(1)] = m.group(2)
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        kind = op[: -len("-start")] if op.endswith("-start") else op
        if kind not in _COLLECTIVES:
            continue
        rest = line[m.end() - 1 :]
        om = _OPERAND_RE.search(rest)
        operands = []
        if om and om.group(1):
            operands = [o.strip() for o in om.group(1).split(",")]
        got = 0
        for name in operands:
            if name in defs:
                got += _types_bytes(defs[name])
        if got == 0:  # fallback: result size (== operand size for all-reduce)
            got = _types_bytes(m.group(2))
        out[kind] += got
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    collective_by_kind: Dict[str, int]
    model_flops_global: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    useful_flops_ratio: float
    peak_fraction: float  # model_flops / (chips * PEAK * t_bound)
    memory_analysis: Dict[str, float]

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1)


def build_roofline(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    cost: Dict[str, float],
    hlo_text: str,
    model_flops_global: float,
    memory_analysis: Optional[Dict[str, float]] = None,
) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    coll_total = float(sum(coll.values()))
    t_c = flops / PEAK_FLOPS
    t_m = hbm / HBM_BW
    t_x = coll_total / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    t_bound = max(t_c, t_m, t_x)
    useful = model_flops_global / (flops * chips) if flops > 0 else 0.0
    peak_frac = (
        model_flops_global / (chips * PEAK_FLOPS * t_bound) if t_bound > 0 else 0.0
    )
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        flops_per_device=flops,
        hbm_bytes_per_device=hbm,
        collective_bytes_per_device=coll_total,
        collective_by_kind={k: v for k, v in coll.items() if v},
        model_flops_global=model_flops_global,
        t_compute=t_c,
        t_memory=t_m,
        t_collective=t_x,
        bottleneck=bottleneck,
        useful_flops_ratio=useful,
        peak_fraction=peak_frac,
        memory_analysis=memory_analysis or {},
    )


def model_flops(cfg, shape, n_params_active: int) -> float:
    """6·N·D for training, 2·N·D for inference steps (dense approximation;
    MoE uses active params)."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_params_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_params_active * tokens
    # decode: one token per sequence
    return 2.0 * n_params_active * shape.global_batch
