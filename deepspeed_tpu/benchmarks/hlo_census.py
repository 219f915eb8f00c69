"""Collective census + wire-byte accounting over compiled HLO text.

Shared by comms_bench (--quant rows), tpu_hlo_check and the lowering
tests — one parser instead of a regex fork each.

Handles both SYNC collectives (`%all-reduce.3 = ...`) and the ASYNC
start/done pairs a latency-hiding backend emits (`%all-reduce-start.3 =
...` + matching `-done`); async ops are counted once, by their start.

Wire-byte model (per device, ring corrections): all-gather /
reduce-scatter / all-to-all move (n-1)/n of the result payload,
all-reduce 2x that (reduce + broadcast phases), collective-permute the
payload.  Absolute numbers are estimates; RATIOS between programs
compiled for the same mesh are exact comparisons.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

__all__ = [
    "COLLECTIVE_OPS",
    "collective_census",
    "collective_wire_bytes",
    "async_overlap_report",
]

COLLECTIVE_OPS = ("all-gather", "all-to-all", "all-reduce",
                  "reduce-scatter", "collective-permute")

_DTYPE_BYTES = {"s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "f64": 8, "pred": 1}

# an op definition: "%all-reduce.3 = <result type> all-reduce(" — async
# starts carry the -start suffix; `-done` lines reference the start's
# buffer and must not double-count
# keyed on the OPCODE, not the instruction name: the TPU compiler names an
# instruction after the jax primitive (%all_to_all.6 = ... all-to-all(...))
_DEF_RE = re.compile(
    r"%[\w.\-]+ = (.*?) (" + "|".join(COLLECTIVE_OPS) + r")(-start)?\(")


def _element_bytes(result_ty: str) -> List[int]:
    """Byte sizes of each dtype[shape] element of an HLO result type
    (one entry for a plain array, several for tuples)."""
    out = []
    for dt, shape in re.findall(r"([a-z0-9]+)\[([\d,]*)\]", result_ty):
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in shape.split(","):
            if d:
                elems *= int(d)
        out.append(elems * _DTYPE_BYTES[dt])
    return out


def _type_bytes(result_ty: str) -> int:
    """Total byte size of an HLO result type (scalar, array, or tuple —
    sums every element, so fused payload+scales tuples are fully
    accounted)."""
    return sum(_element_bytes(result_ty))


def collective_census(txt: str) -> Dict[str, int]:
    """op name -> definition count (async start/done pairs count once)."""
    out = {op: 0 for op in COLLECTIVE_OPS}
    for m in _DEF_RE.finditer(txt):
        out[m.group(2)] += 1
    return out


def collective_wire_bytes(txt: str, world: int) -> float:
    """Estimated per-device wire bytes of one execution (module
    docstring's ring model).  Async starts are counted at the start op.
    A start's result tuple carries both operand and result aliases
    (XLA's convention: (operands..., results...)), and the two halves
    only match in size for all-reduce / collective-permute — all-gather
    results are world x their operands and reduce-scatter results 1/world
    — so the RESULT half is recovered per op: the larger elements for
    all-gather, the smaller for reduce-scatter, half the total for the
    symmetric ops."""
    total = 0.0
    for m in _DEF_RE.finditer(txt):
        result_ty, op, is_start = m.group(1), m.group(2), m.group(3)
        size = _type_bytes(result_ty)
        if is_start and result_ty.lstrip().startswith("("):
            parts = sorted(_element_bytes(result_ty))
            half = len(parts) // 2 or 1
            if op == "all-gather":
                size = float(sum(parts[-half:]))   # results are the large half
            elif op == "reduce-scatter":
                size = float(sum(parts[:half]))    # results are the small half
            else:
                size = size / 2.0
        if op == "all-reduce":
            total += 2.0 * size * (world - 1) / world
        elif op == "reduce-scatter":
            # the RESULT is 1/n of the reduced input; the ring moves
            # (n-1) result-sized chunks per device (group approximated
            # by the world size — exact when the op spans the mesh)
            total += size * (world - 1)
        elif op in ("all-gather", "all-to-all"):
            total += size * (world - 1) / world
        else:
            total += size
    return total


def async_overlap_report(txt: str) -> List[Tuple[str, int, bool]]:
    """Evidence of compute-collective overlap in a SCHEDULED HLO module:
    for every async collective pair, whether real compute (fusion /
    dot / convolution / while, or a custom-call — a Pallas kernel is
    one) is scheduled between the -start and its
    -done.  Returns [(op_name, gap_ops, has_compute_between), ...] —
    empty when the backend emitted no async pairs (e.g. the CPU
    backend), which callers should treat as "no evidence", not failure.
    """
    lines = txt.splitlines()
    starts: Dict[str, Tuple[str, int]] = {}
    out: List[Tuple[str, int, bool]] = []
    start_re = re.compile(
        r"%((?:" + "|".join(COLLECTIVE_OPS) + r")-start[.\d]*) =")
    done_re = re.compile(
        r"(" + "|".join(COLLECTIVE_OPS) + r")-done[.\d]* = .*%("
        r"(?:" + "|".join(COLLECTIVE_OPS) + r")-start[.\d]*)")
    # by opcode, not by instruction name: a Pallas kernel under shard_map
    # is named after the region (%shard_map.26 = ... custom-call(...))
    compute_re = re.compile(
        r" (fusion|dot|convolution|while|custom-call)\(")
    for i, line in enumerate(lines):
        sm = start_re.search(line)
        if sm:
            starts[sm.group(1)] = (sm.group(1).split("-start")[0], i)
            continue
        dm = done_re.search(line)
        if dm and dm.group(2) in starts:
            op, si = starts.pop(dm.group(2))
            gap = lines[si + 1:i]
            has_compute = any(compute_re.search(g) for g in gap)
            out.append((op, i - si - 1, has_compute))
    return out
