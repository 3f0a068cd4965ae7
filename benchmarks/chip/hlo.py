"""Host<->device transfers, recognised from an HLO instruction's text.

The TPU trace names each executed operation by its HLO instruction
("%dynamic-slice-start.21 = ((f32[50304,2048]{1,0:T(8,128)S(5)}, ...),
f32[3144,2048]{...}, u32[]) async-start(...)"). A program moves data
between HBM and pinned host memory with async instructions whose host
side carries the host memory space, `S(5)` in the layout: a
`dynamic-slice` of a host buffer into HBM, a `dynamic-update-slice` of an
HBM slice into a host buffer, or a `copy`, each as a `-start` (or
`async-start`) and a `-done` (or `async-done`) where the program waits
for it. `transfer` names the phase, the direction and the bytes moved.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

HOST_SPACE = "S(5)"
STARTS = ("async-start", "copy-start", "dynamic-slice-start",
          "dynamic-update-slice-start")
DONES = ("async-done", "copy-done", "dynamic-slice-done",
         "dynamic-update-slice-done")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\](\{[^}]*\})?")


def split(text: str) -> Tuple[str, str, str]:
    """'%name = <shape> opcode(operands)...' -> (name, shape, opcode)."""
    name, _, rhs = text.partition(" = ")
    depth, i = 0, 0
    while i < len(rhs):
        c = rhs[i]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    m = re.match(r"\s*([a-z][a-z0-9\-]*)\(", rhs[i:])
    return name.strip().lstrip("%"), rhs[:i], m.group(1) if m else ""


def _arrays(shape: str) -> List[Tuple[int, bool]]:
    """[(bytes, in host memory)] of the non-scalar arrays in a shape."""
    out = []
    for dt, dims, layout in _ARRAY.findall(shape):
        if dt not in DTYPE_BYTES or not dims:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        out.append((n * DTYPE_BYTES[dt], HOST_SPACE in (layout or "")))
    return out


def _top_level(shape: str) -> List[str]:
    """The elements of a tuple shape '(a, (b, c), d)'."""
    if not shape.startswith("("):
        return [shape]
    parts, depth, cur = [], 0, ""
    for c in shape[1:-1]:
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        if c == "," and depth == 0:
            parts.append(cur.strip())
            cur = ""
        else:
            cur += c
    parts.append(cur.strip())
    return parts


def transfer(text: str) -> Optional[Tuple[str, str, int]]:
    """-> (phase "start"|"done", direction "to_device"|"to_host", bytes)
    for a host transfer instruction, else None. Bytes are the HBM-side
    array's: the slice moved, not the host buffer it is cut from."""
    if HOST_SPACE not in text:
        return None
    _, shape, op = split(text)
    if op in STARTS:
        # copy-start: (destination, source, context); the others:
        # ((operands...), destination, context)
        parts = _top_level(shape)
        if len(parts) < 2:
            return None
        to_host = HOST_SPACE in parts[0 if op == "copy-start" else 1]
        dev = [b for b, host in _arrays(shape) if not host]
        return ("start", "to_host" if to_host else "to_device",
                min(dev)) if dev else None
    if op in DONES:
        # the result is the destination; the operand is the started tuple
        dest_host = HOST_SPACE in shape
        operand = text.split(op + "(", 1)[1]
        dev = [b for b, host in _arrays(operand) if not host]
        if not dev and not dest_host:
            dev = [b for b, _ in _arrays(shape)]
        return ("done", "to_host" if dest_host else "to_device",
                min(dev)) if dev else None
    return None


def short_name(text: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12 fusion'."""
    name, _, op = split(text)
    return f"{name} {op}".strip() if op else text[:80]
