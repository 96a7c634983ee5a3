"""Which named device scope each instruction of a compiled module was
traced under.

A layer names its device work with ``bigdl_tpu.telemetry.device_scope``
(``jax.named_scope("bigdl.moe.experts")``); JAX writes the scope path
into the ``op_name`` metadata of every instruction traced inside it,
forward and backward (``.../transpose(jvp(bigdl.moe.experts))/...``), and
a fusion carries the metadata of the operation at its root.  The device
trace names its events after the instructions, so this table lays a
trace's self times under the scopes.  A program with no such scope
gives an empty table.  A count from the module's text: it repeats
exactly."""

from __future__ import annotations

import re

SCOPE_RE = re.compile(r"bigdl\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\bop_name=\"([^\"]*)\"")


def scope_of(op_name: str):
    """The innermost ``bigdl.*`` scope in an ``op_name`` path."""
    found = SCOPE_RE.findall(op_name)
    return found[-1] if found else None


_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")


def instruction_scopes(hlo_text: str, compiler_ops=None) -> dict:
    """``{instruction name: scope}`` for every instruction of the module
    (in any computation) whose metadata names a ``bigdl.*`` scope, and
    for the compiler-made ones ``compiler_ops`` places."""
    out = {}
    for line in hlo_text.splitlines():
        m = _LINE_RE.match(line)
        scope = scope_of(m.group(2)) if m else None
        if scope:
            out[m.group(1)] = scope
        elif compiler_ops:
            n = _NAME_RE.match(line)
            for prefix, placed in compiler_ops.items():
                if n and n.group(1).startswith(prefix):
                    out[n.group(1)] = placed
    return out


_KERNEL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*"
    r"custom_call_target=\"tpu_custom_call\"")


def unscoped_kernels(hlo_text: str, scopes: dict) -> list:
    """The module's ``tpu_custom_call`` instructions (Pallas kernels,
    and the kernels the TPU compiler makes itself, as of a ragged dot)
    that ``scopes`` places nowhere.  A kernel's time read as nobody's
    is a scope's share silently too low: where the layers of a program
    are all named, this list has to be empty (a compiler that renames
    its kernels fills it)."""
    return [m.group(1) for m in map(_KERNEL_RE.match,
                                    hlo_text.splitlines())
            if m and m.group(1) not in scopes]


def seconds_by_scope(op_self_s: dict, scopes: dict) -> dict:
    """Self seconds of a device trace (``trace_reduce``'s ``op_self_s``)
    summed under each scope; instructions under none are left out."""
    totals: dict = {}
    for name, seconds in (op_self_s or {}).items():
        scope = (scopes or {}).get(name)
        if scope:
            totals[scope] = totals.get(scope, 0.0) + seconds
    return totals


def seconds_under(obs: dict, prefix: str):
    """Device seconds of the traced window under the scopes that start
    with ``prefix``, or None where the trace or the table is missing or
    nothing ran under such a scope."""
    dev = obs.get("trace_device0") or {}
    by = seconds_by_scope(dev.get("op_self_s"), obs.get("scopes"))
    found = [s for name, s in by.items() if name.startswith(prefix)]
    return sum(found) if found else None
