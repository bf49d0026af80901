"""Extract roofline terms from a compiled SPMD executable.

collective_bytes is not in cost_analysis(): we parse the post-partitioning
HLO text and sum the result-shape bytes of every collective op, weighted
by the per-device traffic factor of its algorithm (ring all-reduce moves
~2x the buffer; all-gather/reduce-scatter ~1x; all-to-all/permute 1x).
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

# traffic factor per device relative to the buffer size (ring algorithms)
_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_COLLECTIVES = tuple(_FACTOR)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(fragment: str) -> int:
    """Sum bytes of all dtype[dims] arrays in an HLO type fragment."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(fragment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-kind byte totals (per device) + op counts."""
    out = {k: {"bytes": 0.0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        lhs, rhs = ls.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^((?:\([^)]*\))|(?:\w+\[[\d,]*\](?:\{[^}]*\})?))\s+(\S+)\(", rhs)
        if not m:
            continue
        type_frag, opname = m.groups()
        base = opname.split(".")[0]
        # "-start" variants (async collectives)
        base = base.removesuffix("-start")
        if base in _FACTOR:
            b = _shape_bytes(type_frag)
            out[base]["bytes"] += b * _FACTOR[base]
            out[base]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"\b{re.escape(opname)}(?:\.\d+)?\(", hlo_text))


# ------------------------------------------------------- roofline model ---

# Per-chip peaks, keyed by chip name.  TPU v5e: Google Cloud's published
# figures ("TPU v5e" documentation): 197 TFLOP/s bf16, 819 GB/s HBM.  The
# ICI figure is a per-link estimate, not a published peak.  JAX reports
# the chip as device_kind "TPU v5 lite", so that name maps to the same row.
_V5E = {
    "peak_flops": 197e12,   # bf16 FLOP/s per chip
    "hbm_bw": 819e9,        # B/s per chip
    "ici_bw": 50e9,         # B/s per link (estimate)
}
CHIPS = {"TPU v5e": _V5E, "TPU v5 lite": _V5E}


def roofline_terms(chip: str, flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> dict:
    """Roofline times of one program on ``chip``; a chip not in ``CHIPS``
    raises rather than borrowing another chip's peaks."""
    if chip not in CHIPS:
        raise ValueError(f"no peak figures for chip {chip!r}; "
                         f"known: {sorted(CHIPS)}")
    hw = CHIPS[chip]
    t_compute = flops_per_dev / hw["peak_flops"]
    t_memory = bytes_per_dev / hw["hbm_bw"]
    t_collective = coll_bytes_per_dev / hw["ici_bw"]
    terms = {
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_collective": t_collective,
    }
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("t_", "")
    total = max(t_compute, t_memory, t_collective)
    terms["roofline_fraction"] = t_compute / total if total > 0 else 0.0
    return terms
