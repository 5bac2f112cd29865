"""Parse collective traffic out of compiled (SPMD-partitioned) HLO.

``compiled.cost_analysis()`` has no collective-bytes entry, so we walk
the partitioned module text (shapes are PER-DEVICE after SPMD
partitioning) and apply ring-algorithm costs per device:

    all-gather          result R local    -> R * (G-1)/G   (receives rest)
    all-reduce          buffer R local    -> 2R * (G-1)/G  (RS + AG phases)
    reduce-scatter      result R local    -> R * (G-1)     (input = R*G)
    all-to-all          buffer R local    -> R * (G-1)/G
    collective-permute  buffer R local    -> R             (one send)

G = replica-group size parsed from the op.  ``-start``/plain ops are
counted, ``-done`` skipped (async pairs would double count).
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_OP_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Returns {'total': bytes_moved_per_device, per-op dict, 'count': n}."""
    per_op = defaultdict(float)
    counts = defaultdict(int)
    for m in _OP_RE.finditer(hlo_text):
        if m.group("suffix") == "-done":
            continue
        op = m.group("op")
        r = _shape_bytes(m.group("shape"))
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end if line_end > 0 else None]
        g = _group_size(line)
        if op == "all-gather":
            moved = r * (g - 1) / max(g, 1)
        elif op == "all-reduce":
            moved = 2 * r * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            moved = r * (g - 1)
        elif op == "all-to-all":
            moved = r * (g - 1) / max(g, 1)
        else:  # collective-permute
            moved = r
        per_op[op] += moved
        counts[op] += 1
    return {"total": float(sum(per_op.values())),
            "per_op": dict(per_op), "counts": dict(counts)}


def _group_size(line: str) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:  # iota format [num_groups, group_size]
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(len(ids), 1)
    return 2  # collective-permute / unknown: conservative


def hlo_flops_bytes(cost) -> tuple[float, float]:
    """Pull (flops, bytes) out of compiled.cost_analysis()'s dict."""
    cost = cost or {}
    flops = float(cost.get("flops", 0.0))
    bts = float(cost.get("bytes accessed", 0.0))
    return flops, bts
