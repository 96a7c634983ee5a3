"""The reduction from a profiler trace (``*.xplane.pb``) to numbers, with
nothing outside JAX (``jax.profiler.ProfileData``).

A device plane is one named ``/device:TPU:<n>``.  On it, the line
``XLA Ops`` holds one event per executed HLO instruction (named as the
compiled module names it: ``fusion.12``, ``all-reduce.3``,
``custom-call.7``; a ``while`` spans the events of its body, which lie
nested inside it on the same line), and the line ``XLA Modules`` one
event per executed program.  From these:

- ``busy_s``: the union of the intervals in which an operation ran;
- ``window_s``: the traced span the caller gives (host clock between
  start and stop of the trace), or else first start to last end of the
  plane's events;
- ``op_self_s``: per instruction name, its time with the time of the
  events nested inside it taken off, so that a loop does not count its
  body twice;
- ``pallas_s``: the self time of the instructions that are Pallas
  custom calls, by the names the compiled module gives (``op_names``)
  and, failing those, by pattern;
- ``collective_s``: the union of the intervals in which a collective was
  under way: the synchronous ones on ``XLA Ops`` and the start-to-done
  spans on ``Async XLA Ops``.  Total time, overlapped or not;
- ``modules``: per program its events, their seconds, the median length
  of one and the whole executions that the seconds amount to (the
  executions under way when the trace starts and stops are cut short);
- ``idle_gaps``: the longest intervals in which nothing ran.

Run as a script it describes a trace: ``python3 trace_reduce.py <file>``.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # start-to-done spans of async operations
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)")
PALLAS_RE = re.compile(r"^(custom-call|tpu_custom_call)")
_DEVICE_RE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``start_trace`` directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, in s."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, lo=None, hi=None):
    """The intervals in which nothing ran, longest first, as
    ``(length_ns, start_ns)``; ``lo``/``hi`` bound the window."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((s - end, end))
        end = e if end is None else max(end, e)
    if hi is not None and end is not None and hi > end:
        out.append((hi - end, end))
    out.sort(reverse=True)
    return out


def self_times(events) -> dict:
    """``{name: seconds}`` of self time.  ``events`` are
    ``(start_ns, end_ns, name)`` of ONE line; an event that lies inside
    another is its child, and its time is taken off the parent's."""
    totals: dict = {}
    stack = []  # [end_ns, name, self_ns]
    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            _e, n, self_ns = stack.pop()
            totals[n] = totals.get(n, 0) + self_ns
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        _e, n, self_ns = stack.pop()
        totals[n] = totals.get(n, 0) + self_ns
    return {n: v / 1e9 for n, v in totals.items()}


_TEXT_RE = re.compile(r"^%?(\S+) = (.+?) ([\w-]+)\(")


def base_name(name: str) -> str:
    """The instruction's name.  The v5e's trace names an event by the
    instruction's whole text, ``%fusion.12 = f32[8]{0} fusion(...)``;
    other runtimes give the bare name, with or without the sigil."""
    return name.split(" = ", 1)[0].lstrip("%")


def label(name: str) -> str:
    """A short, telling label for the breakdown: name, opcode and the
    start of the result's shape."""
    m = _TEXT_RE.match(name)
    if not m:
        return base_name(name)[:120]
    return f"{m.group(1)} {m.group(3)} {m.group(2)[:60]}"


def _line_events(line):
    out = []
    for ev in line.events:
        s = int(ev.start_ns)
        out.append((s, s + int(ev.duration_ns), ev.name))
    return out


def reduce_plane(plane, window_s=None, op_names=None) -> dict:
    """One device plane → the numbers above."""
    lines = {ln.name: ln for ln in plane.lines}
    ops = _line_events(lines[OPS_LINE]) if OPS_LINE in lines else []
    mods = _line_events(lines[MODULES_LINE]) \
        if MODULES_LINE in lines else []
    busy_from = ops or mods
    if not busy_from:
        return {"plane": plane.name, "busy_s": 0.0, "window_s": window_s,
                "events": 0}
    spans = [(s, e) for s, e, _ in busy_from]
    first = min(s for s, _ in spans)
    last = max(e for _, e in spans)
    busy = union_seconds(spans)
    selfs = self_times((s, e, base_name(n)) for s, e, n in ops)
    full_names: dict = {}
    for _s, _e, n in ops:
        full_names.setdefault(base_name(n), n)
    async_ops = _line_events(lines[ASYNC_LINE]) \
        if ASYNC_LINE in lines else []
    names = op_names or {}
    coll = set(names.get("collective", ()))
    pallas = set(names.get("pallas", ()))

    def is_coll(n):
        return n in coll or bool(COLLECTIVE_RE.match(n))

    def is_pallas(n):
        # with the module's own list, trust it; without, any custom call
        return n in pallas if pallas else bool(PALLAS_RE.match(n))

    durations: dict = {}
    for s, e, n in mods:
        durations.setdefault(n, []).append((e - s) / 1e9)
    # the trace cuts the executions under way at its start and its end
    # short, so whole executions are counted as seconds over the median
    modules = {n: {"count": len(d), "seconds": sum(d),
                   "median_s": statistics.median(d),
                   "whole_executions": sum(d) / statistics.median(d)}
               for n, d in durations.items()}
    return {
        "plane": plane.name,
        "events": len(ops),
        "busy_s": busy,
        "window_s": window_s if window_s else (last - first) / 1e9,
        "span_s": (last - first) / 1e9,
        "op_self_s": selfs,
        "full_names": full_names,
        "collective_s": union_seconds(
            [(s, e) for s, e, n in ops + async_ops
             if is_coll(base_name(n))]),
        "pallas_s": sum(v for n, v in selfs.items() if is_pallas(n)),
        "modules": modules,
        "idle_gaps": [(length / 1e9, (start - first) / 1e9)
                      for length, start in gaps(spans)[:10]],
    }


def device_planes(profile):
    out = []
    for plane in profile.planes:
        m = _DEVICE_RE.match(plane.name)
        if m:
            out.append((int(m.group(2)), plane))
    return [p for _i, p in sorted(out, key=lambda t: t[0])]


def reduce_file(path: str, window_s=None, op_names=None) -> dict:
    """An xplane file → ``{"devices": [per-plane dict, ...], "busy_s":
    mean over the devices that ran anything, "window_s": ...}``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    devs = [reduce_plane(p, window_s, op_names)
            for p in device_planes(profile)]
    ran = [d for d in devs if d["busy_s"] > 0]
    if not ran:
        return {"devices": devs, "busy_s": 0.0, "window_s": window_s}
    return {"devices": devs,
            "busy_s": sum(d["busy_s"] for d in ran) / len(ran),
            "window_s": ran[0]["window_s"]}


def top(selfs: dict, n: int = 10, full_names=None):
    """The ``n`` names with most self time, as ``(label, seconds)``."""
    full_names = full_names or {}
    return [(label(full_names.get(name, name)), s) for name, s in
            sorted(selfs.items(), key=lambda kv: -kv[1])[:n]]


def describe(path: str, out=sys.stdout) -> None:
    """What a trace holds: planes, lines, event counts, the names that
    took most time and one event's stats — to look at before writing
    code against it."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    for plane in profile.planes:
        print(f"plane {plane.name!r}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events", file=out)
            if not evs or not _DEVICE_RE.match(plane.name):
                continue
            tot: dict = {}
            for ev in evs:
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]}", file=out)
            if line.name == MODULES_LINE:
                first = min(ev.start_ns for ev in evs)
                for ev in sorted(evs, key=lambda e: -e.duration_ns)[:14]:
                    print(f"    at {(ev.start_ns - first) / 1e6:10.3f} ms "
                          f"for {ev.duration_ns / 1e6:10.3f} ms  "
                          f"{ev.name[:60]}", file=out)
            stats = {k: str(v)[:160] for k, v in evs[len(evs) // 2].stats}
            print(f"    stats of {evs[len(evs) // 2].name!r}: {stats}",
                  file=out)


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    describe(target)
    red = reduce_file(target)
    for d in red["devices"]:
        print({k: v for k, v in d.items()
               if k not in ("op_self_s", "full_names")})
        print("top self:",
              top(d.get("op_self_s", {}), 10, d.get("full_names")))
