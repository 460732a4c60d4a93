"""Optimized-HLO parsing: collective op inventory and byte counts.

``cost_analysis()`` does not report collective traffic, so §Roofline's third
term comes from summing operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute in ``compiled.as_text()``.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g.  %foo = bf16[16,128,4096]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\s("
    + "|".join(COLLECTIVE_OPS)
    + r")(?:-start|-done)?\("
)
# tuple-shaped outputs: = (bf16[...], bf16[...]) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*("
    + "|".join(COLLECTIVE_OPS)
    + r")(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo_text: str) -> dict:
    """Returns {op_kind: {count, bytes}} + total, parsed from optimized HLO.

    Bytes are the *output* operand sizes (the data a chip must move), summed
    over instructions; -start/-done pairs are deduplicated by only counting
    -start (or the plain op).
    """
    stats: dict = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # counted at -start
        m = _TUPLE_RE.search(line)  # tuple outputs first (subsumes scalar re)
        if m:
            inner, kind = m.groups()
            total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(inner))
            stats[kind]["count"] += 1
            stats[kind]["bytes"] += total
            continue
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            stats[kind]["count"] += 1
            stats[kind]["bytes"] += _shape_bytes(dtype, dims)
    stats["total_bytes"] = int(sum(v["bytes"] for k, v in stats.items() if isinstance(v, dict)))
    stats["total_count"] = int(sum(v["count"] for k, v in stats.items() if isinstance(v, dict)))
    return stats


# ---------------------------------------------------------------------------
# TPU-fusion memory model: HBM traffic ≈ bytes of buffers that MUST
# materialise.  XLA:CPU's "bytes accessed" counts every elementwise operand
# (no fusion), wildly over-stating HBM traffic; on TPU, elementwise chains
# fuse into their producers/consumers.  We approximate materialisation points
# as the outputs of non-fusible ops (dots/convs/reduces/scatter-gather/
# collectives/sorts) plus parameter reads — a standard fusion model.
# ---------------------------------------------------------------------------
# NOTE: "parameter" is deliberately absent — HLO fusion computations re-list
# their operands as parameter lines, which double-counts massively; program
# argument bytes are added once by the caller from memory_analysis().
_MATERIALIZE_OPS = (
    "dot", "convolution", "reduce", "reduce-window", "gather", "scatter",
    "dynamic-slice", "dynamic-update-slice", "sort", "rng",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_MAT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\s("
    + "|".join(_MATERIALIZE_OPS)
    + r")(?:-start|-done)?\("
)


def materialized_bytes(hlo_text: str) -> int:
    """Fusion-model HBM traffic estimate (see block comment)."""
    total = 0
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _MAT_RE.search(line)
        if m:
            dtype, dims, _ = m.groups()
            total += _shape_bytes(dtype, dims)
    return total
