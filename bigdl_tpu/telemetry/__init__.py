"""bigdl_tpu.telemetry — tracing, metrics, and runtime watchdogs.

The observability substrate under the training driver and the serving
engine (ISSUE 6; the foundation BigDL 2.0's cluster pipeline and TVM's
measurement-driven tuning both stand on):

- :class:`Tracer` — step-timeline spans (four top-level spans tile a
  block of the driver's loop: stage_next, dispatch, device_wait,
  replay; planning, host stacking, batch pulls, H2D staging, step
  arguments and triggers nest inside them, the assembler thread's work
  lies beside them on a track of its own — ``PHASE_CATS``), exported
  as Chrome-trace JSON (summarize with ``python -m tools.trace_report
  trace.json``) and mirrored as ``jax.profiler.TraceAnnotation``s, so
  any profiler capture holds them beside the device's timeline; the
  decode service's scheduler thread is tiled the same way
  (``serving/decode.py``: idle, schedule, admit, step on top;
  prefill_launch, splice_launch, first_fetch, step_h2d, dispatch,
  device_wait, step_fetch and emit inside; queue_wait and sequence a
  request, on tracks of their own — ``DECODE_PHASE_CATS``);
- :func:`device_scope` — named DEVICE scopes (``bigdl.moe.experts``)
  for work inside a compiled block, which no host span can see: the
  name lands in the instructions' ``op_name`` metadata;
- :class:`MetricRegistry` — counters, gauges, reservoir histograms with
  p50/p95/p99; ``utils/metrics.Metrics`` and
  ``serving/metrics.ServingMetrics`` are veneers over it;
- watchdogs — :class:`RecompileWatchdog` (GL106 discipline at runtime),
  :class:`StallDetector` (stager starvation / host-sync stalls),
  :class:`MemoryWatermark` (device allocator gauges where available).

Round 2 (ISSUE 11) made the stack externally visible and
request-scoped:

- :class:`RequestContext` — per-request trace context (trace_id,
  tenant, deadline, ReplicaSet hop history) minted at ``submit()``,
  fan-in flow arrows in the Chrome trace;
- :class:`AdminServer` — ``/metrics`` (Prometheus text), ``/healthz``,
  ``/trace``, ``/flight``, ``/profile?seconds=N`` on a loopback-only
  stdlib http thread (``Config.admin_port``, off by default);
- :class:`FlightRecorder` — crash-surviving structured-event JSONL
  stream + bounded ring (``Config.flight_recorder_path``), joined with
  traces by ``python -m tools.obs_report``.

Enable for training via ``Config.telemetry_enabled`` /
``BIGDL_TPU_TELEMETRY=1`` or per-run with
``optimizer.set_telemetry(True, trace_path="trace.json")``.

The whole package is host-side: enabling telemetry adds no dispatch, no
host↔device sync, and leaves the loss sequence bitwise unchanged
(gated in ``tests/test_telemetry.py``).
"""

from bigdl_tpu.telemetry.admin import AdminServer, render_prometheus
from bigdl_tpu.telemetry.context import RequestContext, new_trace_id
from bigdl_tpu.telemetry.flight import FlightRecorder
from bigdl_tpu.telemetry.hooks import DriverTelemetry
from bigdl_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                          MetricRegistry, Reservoir)
from bigdl_tpu.telemetry.scopes import SCOPE_PREFIX, device_scope
from bigdl_tpu.telemetry.tracer import (DECODE_PHASE_CATS,
                                        DECODE_TOP_LEVEL_CATS, NULL_SPAN,
                                        OFF_DRIVER_CATS, PHASE_CATS,
                                        TOP_LEVEL_CATS, Tracer)
from bigdl_tpu.telemetry.watchdog import (MemoryWatermark,
                                          RecompileWatchdog, StallDetector,
                                          jit_cache_size)

__all__ = [
    "AdminServer", "Counter", "DECODE_PHASE_CATS", "DECODE_TOP_LEVEL_CATS",
    "DriverTelemetry", "FlightRecorder", "Gauge",
    "Histogram", "MemoryWatermark", "MetricRegistry", "NULL_SPAN",
    "OFF_DRIVER_CATS", "PHASE_CATS", "RecompileWatchdog", "RequestContext",
    "Reservoir", "SCOPE_PREFIX", "StallDetector", "TOP_LEVEL_CATS",
    "Tracer", "device_scope",
    "jit_cache_size",
    "new_trace_id",
    "render_prometheus",
]
