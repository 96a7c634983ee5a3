"""trace_report — summarize a bigdl_tpu telemetry Chrome trace.

Reads the Chrome-trace JSON the telemetry tracer emits
(``Tracer.dump`` / ``Config.telemetry_trace_path``) and prints the
driver-pipeline picture the raw timeline buries:

- **per-phase time share** — self-time per span category (the lists are
  ``telemetry.tracer.PHASE_CATS`` for a training driver's trace and
  ``DECODE_PHASE_CATS`` for a decode service's) over the trace wall
  clock, plus
  ``other`` for unaccounted time, summing to ~1.  Self-time: nested
  spans (a validation span inside a replay span, ``batch_pull`` inside
  ``host_stack`` inside ``stage_next``) are charged to the child, never
  double-counted;
- **loop coverage** — the whole (not self) time of the four TOP-LEVEL
  categories of a loop, which tile it, over the wall clock: what is
  left is host time no span names.  One figure a loop, never summed:
  ``driver_coverage`` (stage_next / dispatch / device_wait / replay)
  and ``decode_coverage`` (decode_idle / decode_schedule /
  decode_admit / decode_step; two services on one tracer add up);
- **off the loop's thread** — the whole time of the categories that lie
  in no span of the loop (``batch_assemble``: the assembler thread's
  work on each batch; ``decode_queue`` / ``decode_sequence``: a
  request's wait and its life in a slot) over the wall clock.  It
  overlaps the loop's time, so it is no part of the phase share;
- **top spans** — by total duration, with call counts and mean;
- **stall picture** — device-wait fraction (host blocked on device —
  healthy when the device is the bottleneck; the driver's
  ``device_wait``, and the decode scheduler's ``decode_device_wait``
  apart) vs host-stage fraction (device starved by the input pipeline;
  a decode trace has none and reads 0), plus the DISRUPTION count:
  resilience instants (failover, quarantine, replica death, shed,
  breaker trip, rollback) folded in, because a stall picture that
  ignores the failovers that caused the stalls is half a picture;
- **watchdog events** — recompiles, stager starvations, host-sync
  stalls (instant events the watchdogs injected);
- **instant events by category** — EVERY ``ph:"i"`` event grouped by
  its ``cat`` (watchdog / resilience / anything a future subsystem
  emits), so no category is silently ignored; ``--events`` prints the
  chronological listing with args (the incident timeline).

Usage::

    python -m tools.trace_report trace.json
    python -m tools.trace_report trace.json --json
    python -m tools.trace_report trace.json --top 20
    python -m tools.trace_report trace.json --events

Virtual tracks (the ``device`` track carrying in-flight block spans,
category ``pipeline``) overlap the host timeline by design and are
excluded from phase-share accounting — they answer "what was the device
doing", not "where did host time go".
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List

from bigdl_tpu.telemetry.tracer import (DECODE_PHASE_CATS,
                                        DECODE_TOP_LEVEL_CATS, PHASE_CATS,
                                        TOP_LEVEL_CATS)

# both loops' categories: a trace holds the training driver's, the
# decode scheduler's, or (one tracer shared) both
_ALL_CATS = {**PHASE_CATS, **DECODE_PHASE_CATS}
_OFF_LOOP_CATS = tuple(c for c, top in _ALL_CATS.items() if top is None)
# spans on virtual tracks (cat "pipeline", the assembler thread's work,
# a decode request's wait and sequence) overlap the loop's timeline and
# are excluded from the phase accounting
_EXCLUDED_CATS = {"pipeline", *_OFF_LOOP_CATS}


def load_trace(path: str) -> dict:
    """Load a Chrome-trace JSON file; accepts both the object form
    (``{"traceEvents": [...]}``) and a bare event list."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):
        data = {"traceEvents": data}
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(
            f"{path}: not a Chrome trace (no traceEvents key)")
    return data


def _self_times(spans: List[dict]) -> Dict[int, float]:
    """Self time (dur minus nested-child dur) per span index, computed
    per tid with a nesting stack.  Spans from ``with`` blocks on one
    thread nest properly; partial overlap (malformed input) is treated
    as nested-by-start-order, which only redistributes time between the
    overlapping pair."""
    self_us = {i: float(s.get("dur", 0.0)) for i, s in enumerate(spans)}
    by_tid = defaultdict(list)
    for i, s in enumerate(spans):
        by_tid[s.get("tid", 0)].append(i)
    for tid, idxs in by_tid.items():
        idxs.sort(key=lambda i: (spans[i]["ts"], -spans[i].get("dur", 0.0)))
        stack: List[int] = []  # indices of currently-open spans
        for i in idxs:
            ts = spans[i]["ts"]
            while stack and spans[stack[-1]]["ts"] \
                    + spans[stack[-1]].get("dur", 0.0) <= ts:
                stack.pop()
            if stack:  # nested: charge my duration against the parent
                self_us[stack[-1]] -= spans[i].get("dur", 0.0)
            stack.append(i)
    return self_us


def summarize(trace: dict, top: int = 10) -> dict:
    """Aggregate a loaded trace into the report dict (the schema the
    fixture test gates)."""
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    host_spans = [s for s in spans
                  if s.get("cat") not in _EXCLUDED_CATS]
    if not spans:
        raise ValueError("trace contains no complete ('X') spans")
    t0 = min(s["ts"] for s in spans)
    t1 = max(s["ts"] + s.get("dur", 0.0) for s in spans)
    wall_us = max(t1 - t0, 1e-9)

    off_us: Dict[str, float] = defaultdict(float)
    for s in spans:
        if s.get("cat") in _OFF_LOOP_CATS:
            off_us[s["cat"]] += s.get("dur", 0.0)

    self_us = _self_times(host_spans)
    cat_us: Dict[str, float] = defaultdict(float)
    whole_us: Dict[str, float] = defaultdict(float)  # children included
    name_rows: Dict[str, dict] = {}
    for i, s in enumerate(host_spans):
        cat = s.get("cat") or "uncategorized"
        cat_us[cat] += self_us[i]
        whole_us[cat] += s.get("dur", 0.0)
        row = name_rows.setdefault(
            s["name"], {"name": s["name"], "cat": cat, "count": 0,
                        "total_us": 0.0})
        row["count"] += 1
        row["total_us"] += s.get("dur", 0.0)

    share = {c: round(cat_us.get(c, 0.0) / wall_us, 4)
             for c in sorted(cat_us)}
    accounted = sum(share.values())
    share["other"] = round(max(0.0, 1.0 - accounted), 4)
    whole = {c: round(v / wall_us, 4) for c, v in whole_us.items()}

    top_spans = sorted(name_rows.values(),
                       key=lambda r: -r["total_us"])[:top]
    for r in top_spans:
        r["total_ms"] = round(r.pop("total_us") / 1e3, 3)
        r["mean_ms"] = round(r["total_ms"] / r["count"], 4)

    # instants: EVERY category is accounted (a resilience failover or a
    # category some future subsystem invents must not vanish from the
    # report just because this tool predates it)
    watchdog = defaultdict(int)
    resilience = defaultdict(int)
    by_category: Dict[str, Dict[str, int]] = defaultdict(
        lambda: defaultdict(int))
    recompiles = []
    timeline = []
    for e in instants:
        cat = e.get("cat") or "uncategorized"
        by_category[cat][e["name"]] += 1
        if cat == "resilience":
            resilience[e["name"]] += 1
        elif cat in ("watchdog", "uncategorized"):
            watchdog[e["name"]] += 1
        if e["name"] == "recompile":
            recompiles.append(e.get("args", {}))
        timeline.append({"t_ms": round((e["ts"] - t0) / 1e3, 3),
                         "cat": cat, "name": e["name"],
                         "args": e.get("args", {})})
    timeline.sort(key=lambda r: r["t_ms"])

    other = trace.get("otherData", {})
    return {
        "wall_s": round(wall_us / 1e6, 6),
        "span_count": len(spans),
        "dropped_events": other.get("dropped_events", 0),
        "phase_share": share,
        "phase_seconds": {c: round(v / 1e6, 6)
                          for c, v in sorted(cat_us.items())},
        # the top-level categories tile their loop, so their whole
        # time over the wall clock is the share of the run that some
        # span of that loop names (0.0 for a trace without the loop)
        "driver_coverage": round(sum(whole.get(c, 0.0)
                                     for c in TOP_LEVEL_CATS), 4),
        "decode_coverage": round(sum(whole.get(c, 0.0)
                                     for c in DECODE_TOP_LEVEL_CATS), 4),
        "off_driver_share": {c: round(v / wall_us, 4)
                             for c, v in off_us.items()},
        "stall": {
            # whole time: ``stage`` spans hold batch_pull/block_stack
            # children, and the stall picture asks how long the stager
            # held the driver, whoever did the work inside
            "device_wait_fraction": whole.get("device_wait", 0.0),
            "decode_device_wait_fraction": whole.get("decode_device_wait",
                                                     0.0),
            "host_stage_fraction": whole.get("stage", 0.0),
            "dispatch_fraction": whole.get("dispatch", 0.0),
            # the disruption fold (satellite of the admin-plane PR): a
            # wait spike with failovers behind it reads differently
            # from one without
            "disruption_events": int(sum(resilience.values())),
        },
        "recompile_events": recompiles,
        "watchdog_events": dict(watchdog),
        "resilience_events": dict(resilience),
        "events_by_category": {c: dict(n)
                               for c, n in sorted(by_category.items())},
        "event_timeline": timeline,
        "top_spans": top_spans,
    }


def _render(report: dict, events: bool = False) -> str:
    lines = [f"wall {report['wall_s'] * 1e3:.1f} ms, "
             f"{report['span_count']} spans"
             + (f" ({report['dropped_events']} dropped)"
                if report["dropped_events"] else "")]
    lines.append("phase share (self-time / wall; * = top-level):")
    for cat, frac in sorted(report["phase_share"].items(),
                            key=lambda kv: -kv[1]):
        mark = "*" if _ALL_CATS.get(cat) else ""
        lines.append(f"  {cat + mark:<19} {frac * 100:6.2f}%")
    lines.append(f"driver coverage (top-level spans / wall): "
                 f"{report['driver_coverage']:.3f}")
    if report["decode_coverage"]:
        lines.append(f"decode coverage (top-level spans / wall): "
                     f"{report['decode_coverage']:.3f}")
    for cat, frac in report["off_driver_share"].items():
        lines.append(f"off the loop's thread (whole time / wall): "
                     f"{cat} {frac:.3f}")
    st = report["stall"]
    decode_wait = (f", decode_device_wait "
                   f"{st['decode_device_wait_fraction']:.3f}"
                   if st["decode_device_wait_fraction"] else "")
    lines.append(
        f"stall picture: device_wait {st['device_wait_fraction']:.3f}"
        f"{decode_wait} "
        f"(host blocked on device), host_stage "
        f"{st['host_stage_fraction']:.3f} (device starved by input), "
        f"{st['disruption_events']} disruption event(s)")
    if report["watchdog_events"]:
        lines.append("watchdog events: " + ", ".join(
            f"{k}×{v}" for k, v in sorted(
                report["watchdog_events"].items())))
        for r in report["recompile_events"]:
            lines.append(f"  recompile: {r}")
    else:
        lines.append("watchdog events: none")
    if report["resilience_events"]:
        lines.append("resilience events: " + ", ".join(
            f"{k}×{v}" for k, v in sorted(
                report["resilience_events"].items())))
    if events:
        lines.append("instant-event timeline (t from first span):")
        rows = report["event_timeline"]
        for r in rows[:200]:
            args = (" " + json.dumps(r["args"], sort_keys=True)
                    if r["args"] else "")
            lines.append(f"  {r['t_ms']:>10.3f} ms  [{r['cat']}] "
                         f"{r['name']}{args}")
        if len(rows) > 200:
            lines.append(f"  ... {len(rows) - 200} more (use --json)")
    lines.append(f"top spans:")
    w = max((len(r["name"]) for r in report["top_spans"]), default=8)
    lines.append(f"  {'span':<{w}}  {'count':>6}  {'total(ms)':>10}  "
                 f"{'mean(ms)':>9}")
    for r in report["top_spans"]:
        lines.append(f"  {r['name']:<{w}}  {r['count']:>6}  "
                     f"{r['total_ms']:>10.3f}  {r['mean_ms']:>9.4f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tools.trace_report",
        description="Summarize a bigdl_tpu telemetry Chrome trace")
    p.add_argument("trace", help="Chrome-trace JSON file (Tracer.dump)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the report as JSON")
    p.add_argument("--top", type=int, default=10,
                   help="how many top spans to show")
    p.add_argument("--events", action="store_true",
                   help="print the chronological instant-event "
                        "timeline (watchdog + resilience)")
    args = p.parse_args(argv)
    try:
        report = summarize(load_trace(args.trace), top=args.top)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"trace_report: {e}", file=sys.stderr)
        return 2
    print(json.dumps(report) if args.as_json
          else _render(report, events=args.events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
