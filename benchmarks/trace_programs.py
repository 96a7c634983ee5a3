"""A traced window by PROGRAM: where a system runs several executables
in turn (a decode step, a prefill for each bucket, a cache splice), the
``XLA Modules`` line of a device plane holds one event an execution.
``trace_reduce.py`` gives busy seconds and instructions; this gives,
per kind of program, the executions, their seconds and their median,
and names each long idle gap by the programs on either side of it.

A kind is a short name and the substrings that place a module's name
under it (``{"step": ["_step_fn"], ...}``); a module no kind places is
``other``.  Nothing outside JAX is needed.
"""

from __future__ import annotations

import statistics

from benchmarks import trace_reduce


def module_events(path: str, device: int = 0):
    """``[(start_ns, end_ns, name), ...]`` of one device's programs, in
    order of their start; empty where the trace has no such plane."""
    from jax.profiler import ProfileData
    planes = trace_reduce.device_planes(ProfileData.from_file(path))
    if device >= len(planes):
        return []
    for line in planes[device].lines:
        if line.name == trace_reduce.MODULES_LINE:
            return sorted(trace_reduce._line_events(line))
    return []


def kind_of(name: str, kinds: dict) -> str:
    for kind, needles in kinds.items():
        if any(n in name for n in needles):
            return kind
    return "other"


def by_kind(events, kinds: dict) -> dict:
    """Per kind: ``count``, ``seconds``, ``median_s``, ``min_s`` and
    ``max_s`` of its executions."""
    durations: dict = {}
    for s, e, name in events:
        durations.setdefault(kind_of(name, kinds), []).append((e - s) / 1e9)
    return {k: {"count": len(d), "seconds": sum(d),
                "median_s": statistics.median(d), "min_s": min(d),
                "max_s": max(d)}
            for k, d in durations.items()}


def _gaps(events, kinds: dict):
    """``(name, seconds)`` of every interval between two programs in
    which none ran, named ``after_<kind>.before_<kind>``."""
    for (_s0, e0, n0), (s1, _e1, n1) in zip(events, events[1:]):
        if s1 > e0:
            yield (f"after_{kind_of(n0, kinds)}.before_"
                   f"{kind_of(n1, kinds)}", (s1 - e0) / 1e9)


def named_gaps(events, kinds: dict, n: int = 10):
    """The ``n`` longest such intervals, one entry a gap, longest
    first: what the contract's ``breakdown.idle_gaps`` asks for."""
    return sorted(_gaps(events, kinds), key=lambda g: -g[1])[:n]


def gap_totals(events, kinds: dict) -> dict:
    """Idle seconds and gap counts by the same names, over the whole
    trace: where the host's share of the window goes."""
    totals: dict = {}
    for name, seconds in _gaps(events, kinds):
        t = totals.setdefault(name, {"count": 0, "seconds": 0.0})
        t["count"] += 1
        t["seconds"] += seconds
    return totals
