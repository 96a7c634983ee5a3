"""Counts made from a compiled module's HLO text: the bytes each kind of
collective puts on the wire, and the names of the instructions that are
collectives or Pallas custom calls (the trace names its events after
them).  The byte arithmetic is a copy of ``tools/byte_audit.py``
``collective_wire_bytes`` (PR 21 tree), kept here so that no later PR can
change the yardstick.  A count: it repeats exactly."""

from __future__ import annotations

import re
from collections import defaultdict

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# "name = shape opcode(": the shape may be a tuple with spaces inside
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w-]+)\(")


def _sizes(shape_str: str) -> list:
    out = []
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * DTYPE_BYTES[dt])
    return out


def shape_bytes(shape_str: str) -> int:
    return sum(_sizes(shape_str))


def _operand_text(line: str, start: int) -> str:
    """The operand list from ``start`` (just past the opcode's opening
    paren) to its MATCHING close paren."""
    depth = 1
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return line[start:i]
    return line[start:]


def _base(opcode: str) -> str:
    return opcode[:-6] if opcode.endswith("-start") else opcode


def collective_wire_bytes(hlo_text: str) -> dict:
    """``{kind: bytes, ..., "total": sum}`` over the WHOLE module; a loop
    body counts once.  all-reduce / all-gather / collective-permute /
    all-to-all: result bytes; reduce-scatter: operand bytes; an async
    ``*-start``: the largest element of its (operand, result) tuple;
    ``*-done`` is skipped, its start was charged."""
    by_kind: dict = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        _name, shape_str, opcode = m.groups()
        if opcode.endswith("-done") or _base(opcode) not in COLLECTIVE_KINDS:
            continue
        if _base(opcode) == "reduce-scatter":
            b = shape_bytes(_operand_text(line, m.end())) \
                or shape_bytes(shape_str)
        elif opcode.endswith("-start"):
            b = max(_sizes(shape_str), default=0)
        else:
            b = shape_bytes(shape_str)
        if b:
            by_kind[_base(opcode)] += b
    out = dict(by_kind)
    out["total"] = sum(by_kind.values())
    return out


def op_names(hlo_text: str) -> dict:
    """``{"collective": [...], "pallas": [...]}``: instruction names by
    what they are.  A fusion that wraps a collective or a kernel is
    named too, since the trace shows the fusion."""
    coll, pallas = [], []
    computations: dict = {}
    current = None
    calls = []  # (instruction name, called computation)
    for line in hlo_text.splitlines():
        head = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                        line)
        if head:
            current = head.group(1)
            computations[current] = {"collective": False, "pallas": False}
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, _shape, opcode = m.groups()
        base = opcode[:-5] if opcode.endswith("-done") else _base(opcode)
        is_coll = base in COLLECTIVE_KINDS
        is_pallas = opcode == "custom-call" and "tpu_custom_call" in line
        if is_coll:
            coll.append(name)
        if is_pallas:
            pallas.append(name)
        if current is not None:
            computations[current]["collective"] |= is_coll
            computations[current]["pallas"] |= is_pallas
        if opcode == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if called:
                calls.append((name, called.group(1)))
    for name, comp in calls:
        flags = computations.get(comp, {})
        if flags.get("collective"):
            coll.append(name)
        if flags.get("pallas"):
            pallas.append(name)
    return {"collective": sorted(set(coll)), "pallas": sorted(set(pallas))}
