"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch, shape, mesh), all in seconds, against the peaks of
the chip named by its ``device_kind`` (:data:`PEAKS`):

  compute    = HLO_FLOPs_per_dev / bf16 FLOP/s
  memory     = HLO_bytes_per_dev / HBM bytes/s
  collective = collective_bytes_per_dev / bytes/s of one ICI link

HLO_FLOPs / bytes come from ``compiled.cost_analysis()`` (per-partition for
SPMD modules).  Collective bytes are NOT in cost_analysis: we parse the
post-partitioning optimized HLO (``compiled.as_text()``) and sum operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (per-partition shapes -> per-device bytes).
"""

from __future__ import annotations

import re
from typing import Any, Dict

import numpy as np

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interchip
# interconnect over the 4 links of a 2-D torus -> 50 GB/s per link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_link_bytes_per_s": 50e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row for ``device_kind``; a chip not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add a row with its source)"
        ) from None

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

# "%name = TYPE opcode(...)" where TYPE is e.g. f32[8,128]{1,0} or a tuple
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)\)",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in (partitioned) HLO text.

    Returns {"total": int, "by_op": {op: bytes}, "count": {op: n}}.
    Operand sizes are resolved via a symbol table of instruction result
    types; literals/params inline in operand lists are rare for collectives.
    """
    symbols: Dict[str, str] = {}
    instrs = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, opcode, operands = m.groups()
        symbols[name] = type_str
        instrs.append((name, type_str, opcode, operands))

    by_op: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    count: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    for name, type_str, opcode, operands in instrs:
        base = None
        for op in COLLECTIVE_OPS:
            if opcode == op or opcode.startswith(op + "-"):  # e.g. all-gather-start
                base = op
                break
        if base is None:
            continue
        if opcode.endswith("-done"):
            continue  # paired with -start; avoid double count
        # operand references: %name or plain name tokens before any attrs
        ops_bytes = 0
        for ref in re.findall(r"%?([\w\.\-]+)", operands.split("),")[0]):
            if ref in symbols:
                ops_bytes += _shape_bytes(symbols[ref])
        if ops_bytes == 0:
            # fall back to result size (e.g. operands not in table)
            ops_bytes = _shape_bytes(type_str)
        by_op[base] += ops_bytes
        count[base] += 1
    return {
        "total": int(sum(by_op.values())),
        "by_op": {k: int(v) for k, v in by_op.items() if v},
        "count": {k: int(v) for k, v in count.items() if v},
    }


def model_flops(n_params: int, n_active_params: int, tokens: int, kind: str) -> float:
    """6*N*D for training, 2*N*D for inference forward (N = active params)."""
    n = n_active_params or n_params
    return (6.0 if kind == "train" else 2.0) * n * tokens


def roofline_terms(
    *,
    device_kind: str,
    flops_per_dev: float,
    bytes_per_dev: float,
    coll_bytes_per_dev: float,
) -> Dict[str, Any]:
    peak = peaks(device_kind)
    t_c = flops_per_dev / peak["bf16_flops"]
    t_m = bytes_per_dev / peak["hbm_bytes_per_s"]
    t_x = coll_bytes_per_dev / peak["ici_link_bytes_per_s"]
    dominant = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    bound = max(t_c, t_m, t_x)
    return {
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_collective_s": t_x,
        "dominant": dominant,
        "roofline_fraction": (t_c / bound) if bound > 0 else 0.0,
    }


def active_param_count(cfg, pspecs) -> int:
    """Active params per token (MoE: only top_k experts count)."""
    from repro.models.param import count_params

    total = count_params(pspecs)
    if cfg.family != "moe" or cfg.num_experts == 0:
        return total
    # expert weights: [E, d, f] x3 per layer
    expert_per_layer = 3 * cfg.num_experts * cfg.d_model * cfg.d_ff
    expert_total = cfg.num_layers * expert_per_layer
    active_expert = expert_total * cfg.top_k / cfg.num_experts
    return int(total - expert_total + active_expert)
