"""The program's own spans, read back from a profiler trace
(``*.xplane.pb``) and laid against the device's idle gaps.

Every span of an enabled ``bigdl_tpu.telemetry.Tracer`` is also a
``jax.profiler.TraceAnnotation`` named ``bigdl:<category>:<name>``, so a
trace taken while the tracer is on holds them on the host plane
(``/host:CPU``), one line a thread, nested as entered, on the clock the
device planes use.  From such a file:

- ``threads``: the ``bigdl:`` events of every host line that has any;
  the driver's thread is the one that holds a ``dispatch`` span;
- ``categories``: per category the seconds of its spans and, by
  ``trace_reduce.self_times``, their self seconds (children taken off);
- ``gaps``: for each of the ten longest idle gaps of device 0
  (``trace_reduce.gaps`` over its ``XLA Ops`` events, on the plane's own
  clock), the seconds of the gap under each INNERMOST span of the
  driver's thread that overlaps it, by span name, and the remainder that
  no span covers as ``unspanned``.  The parts sum to the gap's length.

A trace from a program without the mirror holds no ``bigdl:`` event; the
reduction then returns empty tables and every gap is ``unspanned``.

    python3 benchmarks/host_spans.py <file or dir> [--json]
"""

from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import trace_reduce  # noqa: E402

PREFIX = "bigdl:"
DRIVER_SPAN = "dispatch"    # only the driver's thread dispatches blocks
UNSPANNED = "unspanned"
N_GAPS = 10


def parse(event_name: str):
    """``bigdl:<category>:<name>`` → ``(category, name)``."""
    _, cat, name = event_name.split(":", 2)
    return cat or "uncategorized", name


def host_threads(profile) -> list:
    """``[{"plane", "line", "events": [(start_ns, end_ns, category,
    name)]}]``: one entry per host line that holds ``bigdl:`` events."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    events.append((s, s + int(ev.duration_ns),
                                   *parse(ev.name)))
            if events:
                out.append({"plane": plane.name, "line": line.name,
                            "events": sorted(events)})
    return out


def driver_thread(threads: list):
    """The thread that holds a ``dispatch`` span; None without one."""
    for th in threads:
        if any(name == DRIVER_SPAN for _s, _e, _c, name in th["events"]):
            return th
    return None


def category_seconds(threads: list) -> dict:
    """``{category: {"seconds", "self_seconds", "spans"}}`` over every
    thread.  Self time is taken line by line: a span's children are the
    spans inside it on its own thread."""
    out: dict = {}
    for th in threads:
        for s, e, cat, _name in th["events"]:
            row = out.setdefault(cat, {"seconds": 0.0, "self_seconds": 0.0,
                                       "spans": 0})
            row["seconds"] += (e - s) / 1e9
            row["spans"] += 1
        selfs = trace_reduce.self_times(
            (s, e, cat) for s, e, cat, _name in th["events"])
        for cat, seconds in selfs.items():
            out[cat]["self_seconds"] += seconds
    return out


def innermost_segments(events) -> list:
    """One thread's nested spans, flattened: disjoint ``(start_ns,
    end_ns, name)`` pieces in time order, each named by the innermost
    span open there."""
    segments = []
    stack = []   # [end_ns, name] of the spans open at the cursor
    cursor = None

    def advance(to):
        nonlocal cursor
        if to > cursor:
            if stack:
                segments.append((cursor, to, stack[-1][1]))
            cursor = to

    for s, e, _cat, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        if cursor is None:
            cursor = s
        advance(s)
        stack.append([e, name])
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return segments


def attribute(gap_start: int, gap_end: int, segments) -> dict:
    """Seconds of ``[gap_start, gap_end)`` under each segment name, and
    what no segment covers as ``unspanned``."""
    out: dict = {}
    covered = 0
    for s, e, name in segments:
        lo, hi = max(s, gap_start), min(e, gap_end)
        if hi > lo:
            out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
            covered += hi - lo
    rest = (gap_end - gap_start) - covered
    if rest > 0:
        out[UNSPANNED] = rest / 1e9
    return out


def device0_busy(profile) -> list:
    """``(start_ns, end_ns)`` of what ran on device 0, on the plane's
    own clock (``trace_reduce`` shifts its gaps to the first event; the
    host's spans are not shifted, so neither are these)."""
    planes = trace_reduce.device_planes(profile)
    if not planes:
        return []
    lines = {ln.name: ln for ln in planes[0].lines}
    for name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
        if name in lines:
            spans = [(s, e) for s, e, _name in
                     trace_reduce._line_events(lines[name])]
            if spans:
                return spans
    return []


def reduce_file(path: str) -> dict:
    """An xplane file → ``{"threads", "driver_line", "categories",
    "gaps", "gap_seconds", "gap_named_share"}``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    threads = host_threads(profile)
    driver = driver_thread(threads)
    segments = innermost_segments(driver["events"]) if driver else []
    busy = device0_busy(profile)
    first = min((s for s, _e in busy), default=0)
    gaps = []
    for length, start in trace_reduce.gaps(busy)[:N_GAPS]:
        gaps.append({"at_s": (start - first) / 1e9,
                     "seconds": length / 1e9,
                     "by_span": attribute(start, start + length,
                                          segments)})
    total = sum(g["seconds"] for g in gaps)
    named = total - sum(g["by_span"].get(UNSPANNED, 0.0) for g in gaps)
    return {
        "threads": [{"plane": th["plane"], "line": th["line"],
                     "spans": len(th["events"])} for th in threads],
        "driver_line": driver["line"] if driver else None,
        "categories": category_seconds(threads),
        "gaps": gaps,
        "gap_seconds": total,
        "gap_named_share": named / total if total else None,
    }


def render(red: dict) -> str:
    lines = [f"host threads with {PREFIX} spans: "
             + (", ".join(f"{t['line']} ({t['spans']})"
                          for t in red["threads"]) or "none")
             + f"; driver: {red['driver_line']}"]
    lines.append(f"{'category':<14} {'spans':>6} {'seconds':>10} "
                 f"{'self s':>10}")
    for cat, row in sorted(red["categories"].items(),
                           key=lambda kv: -kv[1]["seconds"]):
        lines.append(f"{cat:<14} {row['spans']:>6} "
                     f"{row['seconds']:>10.4f} {row['self_seconds']:>10.4f}")
    lines.append(f"the {len(red['gaps'])} longest idle gaps of device 0 "
                 f"(at = seconds after its first event):")
    for g in red["gaps"]:
        parts = ", ".join(f"{name} {s * 1e3:.2f}" for name, s in sorted(
            g["by_span"].items(), key=lambda kv: -kv[1]))
        lines.append(f"  at {g['at_s']:9.4f} s  {g['seconds'] * 1e3:10.3f} "
                     f"ms: {parts}")
    share = red["gap_named_share"]
    lines.append(f"named share of these gaps: "
                 + (f"{share:.4f} of {red['gap_seconds']:.4f} s"
                    if share is not None else "no gap"))
    return "\n".join(lines)


def main(argv) -> int:
    as_json = "--json" in argv
    args = [a for a in argv if a != "--json"]
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    target = args[0]
    if os.path.isdir(target):
        target = trace_reduce.find_xplane(target)
    red = reduce_file(target)
    print(json.dumps(red) if as_json else render(red))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
