"""Step-timeline tracer — nested host-side spans, Chrome-trace export.

The reference lineage self-times every layer (``AbstractModule.getTimes``)
and prints driver-phase accumulators (``Metrics.summary``).  Under XLA
those observables fused away; what remains measurable is the *pipeline*:
host batch stacking, H2D staging, jit dispatch, device wait, the
one-block-behind loss fetch, trigger/validation/checkpoint work.  This
tracer records exactly those phases as spans and exports them as
Chrome-trace JSON (open in Perfetto / ``chrome://tracing``, summarize
with ``tools/trace_report.py``).

Every span of an enabled tracer is ALSO a
``jax.profiler.TraceAnnotation`` named ``bigdl:<category>:<name>`` with
the span's scalar arguments as its keywords.  The annotation records
nothing unless a profiler session is running; when one is (the
benchmark's traced run, an operator's ``/profile?seconds=N`` capture of
a live process) the program's spans land on the ``/host:CPU`` plane of
the xplane, one line a thread, nested as entered, on the device trace's
own clock — so a device idle gap can be laid against what the host was
doing in it (``benchmarks/host_spans.py``).  ``record()``, ``instant()``
and the flow events have no live scope and are not mirrored.

The hard contract — telemetry is PROVABLY INERT:

- a span is two ``time.perf_counter_ns()`` reads, one list append and
  one annotation — no device work, no host↔device sync, ever;
  ``jax.profiler`` is imported when the first enabled tracer is made,
  never on the off path;
- spans around device fetches wrap fetches the driver already performs
  (the one-block-behind loss fetch — the GL107-safe pattern), never
  introduce one;
- disabled (``enabled=False``), ``span()`` returns one shared no-op
  context manager: zero allocation, zero branching beyond the flag —
  the loss sequence and dispatch count are bitwise identical either way
  (gated in ``tests/test_telemetry.py``).

Event volume is bounded: past ``capacity`` events the tracer drops and
counts (``dropped_events`` rides in the export) — an always-on run may
not grow memory with step count.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional, Tuple


class _NullSpan:
    """Shared no-op context manager returned by a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()

# The span categories the training driver and its stager emit, each with
# whether it is TOP-LEVEL: the four top-level categories tile every
# iteration of the driver's loop (one span of each a block, carrying
# ``block=<dispatch index>``); the others lie inside a top-level span
# (``plan``, ``stage``, ``step_args`` in ``stage_next``;
# ``buffer_return``, ``batch_pull`` and ``block_stack`` in the ``stage``
# span ``host_stack``; ``trigger`` in ``replay``).  ``None`` marks a
# category that lies in NO span of the driver: ``batch_assemble`` is the
# assembler thread's work on a batch (``assemble``, on the ``assembler``
# track, recorded by the stager from the stamps the batch carries),
# which overlaps the driver's time.  tools/trace_report.py takes the
# list from here.
PHASE_CATS = {
    "stage_next": True, "plan": False, "stage": False,
    "buffer_return": False, "batch_pull": False, "block_stack": False,
    "step_args": False,
    "dispatch": True, "device_wait": True, "replay": True,
    "trigger": False, "batch_assemble": None,
}
TOP_LEVEL_CATS = tuple(c for c, top in PHASE_CATS.items() if top)
OFF_DRIVER_CATS = tuple(c for c, top in PHASE_CATS.items() if top is None)

# The same table for the decode service's scheduler thread
# (``serving/decode.py``): four top-level categories tile every pass of
# its loop (``idle``, ``schedule``, one ``admit`` a prefill, one ``step``
# carrying ``step=<index>``); the others lie inside an ``admit``
# (``prefill_launch`` and ``splice_launch`` in ``decode_launch``,
# ``first_fetch`` in ``decode_fetch``, ``emit``) or a ``step``
# (``step_h2d``, ``dispatch`` in ``decode_launch``, ``device_wait``,
# ``step_fetch`` in ``decode_fetch``, ``emit``).  ``None``: a request's
# own spans, recorded on virtual tracks (``queue_wait`` on ``queue``,
# ``sequence`` on ``slot-<i>``), which overlap the scheduler's time.
DECODE_PHASE_CATS = {
    "decode_idle": True, "decode_schedule": True, "decode_admit": True,
    "decode_step": True,
    "decode_h2d": False, "decode_launch": False,
    "decode_device_wait": False, "decode_fetch": False,
    "decode_emit": False,
    "decode_queue": None, "decode_sequence": None,
}
DECODE_TOP_LEVEL_CATS = tuple(c for c, top in DECODE_PHASE_CATS.items()
                              if top)

_SCALARS = (bool, int, float, str)


def _trace_annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class _Span:
    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_ann", "dur_ns")

    def __init__(self, tracer: "Tracer", name: str, cat: Optional[str],
                 args: Optional[dict]):
        self._tr = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        # the annotation encloses the clock reads, so spans nest in the
        # xplane exactly as they nest on the host clock
        scalars = {k: v for k, v in self.args.items()
                   if isinstance(v, _SCALARS)} if self.args else {}
        self._ann = self._tr._annotation(
            f"bigdl:{self.cat or ''}:{self.name}", **scalars)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        # kept for a caller that sums its own spans as they close
        # (``serving/decode.py``), whatever the buffer still holds
        self.dur_ns = t1 - self._t0
        self._tr._record("X", self.name, self.cat, self._t0,
                         self.dur_ns, self.args)
        return False

    def set(self, **args) -> None:
        """Arguments known only inside the span (a count of what it
        did).  They reach the recorded event; the annotation took its
        keywords at entry."""
        self.args = {**self.args, **args} if self.args else args


class Tracer:
    """Thread-safe span recorder with Chrome-trace JSON export.

    Events are stored as tuples ``(ph, name, cat, t0_ns, dur_ns, tid,
    args, flow)`` where ``ph`` is the Chrome phase ("X" complete span,
    "i" instant, "s"/"f" flow start/finish) and ``tid`` is either a
    host thread id or a virtual track name (the driver puts in-flight
    device blocks on a ``"device"`` track so they can overlap host
    spans without breaking nesting).  ``flow`` is the flow-arrow id for
    "s"/"f" events (None otherwise) — the serving engine uses flows to
    fan N coalesced request spans into their one dispatch span.
    """

    def __init__(self, enabled: bool = True, capacity: int = 200_000):
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._events: List[Tuple] = []  # guarded-by: _lock
        self._dropped = 0               # write-guarded-by: _lock
        # the mirror's annotation class: imported when a tracer is made
        # enabled (or first spans after ``enabled`` was switched on),
        # never on the off path
        self._annotation = _trace_annotation() if self.enabled else None

    # -- recording ---------------------------------------------------------
    def span(self, name: str, cat: Optional[str] = None, **args):
        """Context manager timing one host-side phase.  ``cat`` groups
        spans into pipeline phases (see ``PHASE_CATS``); ``args`` ride
        into the Chrome-trace ``args`` field (keep them cheap scalars)."""
        if not self.enabled:
            return NULL_SPAN
        if self._annotation is None:
            self._annotation = _trace_annotation()
        return _Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "watchdog", **args) -> None:
        """Point-in-time marker (watchdog events: recompile, stall)."""
        if not self.enabled:
            return
        self._record("i", name, cat, time.perf_counter_ns(), 0,
                     args or None)

    def flow_start(self, name: str, fid: int, cat: Optional[str] = None,
                   **args) -> None:
        """Open one side of a Chrome flow arrow (``ph:"s"``).  Emit it
        INSIDE an open span on the emitting thread — flow events bind to
        the enclosing slice whose time range contains them.  ``fid``
        pairs starts with finishes (``telemetry.context.flow_id``); the
        request-fan-in edges in the serving trace are N ``flow_start``s
        (one per coalesced request's submit span) finishing in the one
        dispatch span."""
        if not self.enabled:
            return
        self._record("s", name, cat, time.perf_counter_ns(), 0,
                     args or None, flow=fid)

    def flow_end(self, name: str, fid: int, cat: Optional[str] = None,
                 **args) -> None:
        """Close a flow arrow (``ph:"f"``, binding to the ENCLOSING
        slice — ``bp:"e"``); emit inside the consuming span."""
        if not self.enabled:
            return
        self._record("f", name, cat, time.perf_counter_ns(), 0,
                     args or None, flow=fid)

    def record(self, name: str, t0_ns: int, t1_ns: int,
               cat: Optional[str] = None, track: Optional[str] = None,
               **args) -> None:
        """Record a span with explicit endpoints — for durations whose
        start predates the call site (e.g. a dispatched block's
        in-flight window, closed by the one-block-behind fetch).
        ``track`` places it on a named virtual track instead of the
        calling thread."""
        if not self.enabled:
            return
        self._record("X", name, cat, t0_ns, max(0, t1_ns - t0_ns),
                     args or None, tid=track)

    def _record(self, ph, name, cat, t0_ns, dur_ns, args, tid=None,
                flow=None):
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            if len(self._events) >= self.capacity:
                self._dropped += 1
                return
            self._events.append((ph, name, cat, t0_ns, dur_ns, tid, args,
                                 flow))

    # -- reading -----------------------------------------------------------
    def events(self) -> List[Tuple]:
        with self._lock:
            return list(self._events)

    @property
    def dropped_events(self) -> int:
        return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    # -- export ------------------------------------------------------------
    def to_chrome_trace(self, process_name: str = "bigdl_tpu") -> dict:
        """Chrome-trace JSON object (``ts``/``dur`` in microseconds,
        which is what Perfetto and ``chrome://tracing`` expect)."""
        events = self.events()
        tid_map: Dict[object, int] = {}

        def tid_of(tid) -> int:
            if tid not in tid_map:
                # virtual tracks get small ids after the host threads
                tid_map[tid] = len(tid_map) + 1
            return tid_map[tid]

        out = []
        for ph, name, cat, t0_ns, dur_ns, tid, args, flow in events:
            ev = {"name": name, "ph": ph, "pid": 0, "tid": tid_of(tid),
                  "ts": t0_ns / 1e3}
            if ph == "X":
                ev["dur"] = dur_ns / 1e3
            elif ph in ("s", "f"):
                # flow arrow: id pairs the start with its finish; "f"
                # binds to the ENCLOSING slice (bp:"e") so the arrow
                # lands on the dispatch span, not the next slice
                ev["id"] = flow
                if ph == "f":
                    ev["bp"] = "e"
            else:
                ev["s"] = "t"
            if cat:
                ev["cat"] = cat
            if args:
                ev["args"] = args
            out.append(ev)
        meta = [{"name": "process_name", "ph": "M", "pid": 0,
                 "args": {"name": process_name}}]
        for tid, small in sorted(tid_map.items(), key=lambda kv: kv[1]):
            label = tid if isinstance(tid, str) else f"host-{small}"
            meta.append({"name": "thread_name", "ph": "M", "pid": 0,
                         "tid": small, "args": {"name": label}})
        return {"traceEvents": meta + out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self._dropped,
                              "span_count": len(out)}}

    def dump(self, path: str, process_name: str = "bigdl_tpu") -> str:
        """Write the Chrome-trace JSON to ``path`` and return it."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(process_name), f)
        return path
