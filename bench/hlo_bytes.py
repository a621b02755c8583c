"""Bytes an HLO instruction must move, from the shapes in its text.

The trace names each device op by its instruction text, result and operand
shapes included (``%segment_combine.1 = f32[1,4096]{..} custom-call(s32[64]
{..} %a, ...), custom_call_target=...``).  The least HBM traffic of a call
is reading each operand once and writing its result once.
"""

from __future__ import annotations

import math
import re

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(ITEMSIZE) + r")\[([0-9,]*)\]")


def shape_bytes(text: str) -> int:
    return sum(ITEMSIZE[t] * math.prod(int(d) for d in dims.split(",") if d)
               for t, dims in _SHAPE.findall(text))


def call_bytes(text: str, op: str = "custom-call") -> int:
    """Result bytes plus operand bytes of one ``op`` instruction's text."""

    head, sep, rest = text.partition(f" {op}(")
    if not sep:
        raise ValueError(f"not a {op} instruction: {text[:120]!r}")
    depth = 1
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            break
    operands = rest[:i]
    if not _SHAPE.search(operands):
        raise ValueError(f"operands carry no shapes: {text[:120]!r}")
    return shape_bytes(head.split(" = ", 1)[-1]) + shape_bytes(operands)
