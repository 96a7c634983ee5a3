"""Telemetry subsystem tests (ISSUE-6 acceptance surface).

- tracer: span nesting/ordering, Chrome-trace JSON schema, disabled
  no-op path, bounded capacity;
- registry: counters/gauges/histograms, thread safety under concurrent
  submit, snapshot schema;
- Metrics back-compat: the ``summary()`` string format is unchanged by
  the registry rebase;
- serving: per-row-bucket latency reservoirs in ``stats()``;
- watchdogs: recompile positive (seeded shape-churn jit loop) and
  negative (AOT-warmed serving path), stall detector semantics, memory
  watermark degrades silently off-TPU;
- THE INERTNESS GATE: with telemetry enabled, the per-step loss
  sequence is BITWISE identical and the dispatch count equal to
  telemetry-off, for K ∈ {1, 4};
- driver span coverage: the four top-level categories tile the driver
  loop, children nest in their parents, one block number rides from a
  block's staging to its replay (K ∈ {1, 4} × inline / threaded batch
  assembly); "stage" means ``stager.take`` for the stall detector too;
- the profiler mirror: every span of an enabled tracer comes back from
  a ``jax.profiler`` capture as ``bigdl:<cat>:<name>``;
- trace_report: fixture-driven summary (phase shares sum to ~1,
  self-time attribution, watchdog events) and CLI exit codes.
"""

import glob
import json
import math
import os
import threading
import time

import jax
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import (DataSet, MTSampleToMiniBatch,
                               SampleToMiniBatch)
from bigdl_tpu.dataset import image, mnist
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.telemetry import (MemoryWatermark, MetricRegistry,
                                 RecompileWatchdog, Reservoir,
                                 StallDetector, Tracer, jit_cache_size)
from bigdl_tpu.telemetry.tracer import (NULL_SPAN, OFF_DRIVER_CATS,
                                        TOP_LEVEL_CATS)
from bigdl_tpu.utils.metrics import Metrics
from tools import trace_report

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


# ==========================================================================
# tracer
# ==========================================================================
class TestTracer:
    def test_span_nesting_and_ordering(self):
        t = Tracer()
        with t.span("outer", cat="replay"):
            with t.span("inner", cat="trigger"):
                pass
            with t.span("inner2", cat="trigger"):
                pass
        evs = t.events()  # (ph, name, cat, t0_ns, dur_ns, tid, args)
        names = [e[1] for e in evs]
        # spans are recorded at EXIT: children land before their parent
        assert names == ["inner", "inner2", "outer"]
        by = {e[1]: e for e in evs}
        out0, outd = by["outer"][3], by["outer"][4]
        for child in ("inner", "inner2"):
            c0, cd = by[child][3], by[child][4]
            assert c0 >= out0
            assert c0 + cd <= out0 + outd  # nested inside the parent
        # siblings are ordered
        assert by["inner"][3] + by["inner"][4] <= by["inner2"][3]

    def test_chrome_trace_schema(self, tmp_path):
        t = Tracer()
        with t.span("dispatch", cat="dispatch", k=4):
            pass
        t.instant("recompile", key="x")
        t.record("block_inflight", 1000, 5000, cat="pipeline",
                 track="device", steps=2)
        path = t.dump(str(tmp_path / "trace.json"))
        data = json.load(open(path))
        assert set(data) == {"traceEvents", "displayTimeUnit", "otherData"}
        evs = data["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        # the virtual device track is NAMED in the thread metadata
        assert any(e["name"] == "thread_name"
                   and e["args"]["name"] == "device" for e in metas)
        xs = [e for e in evs if e["ph"] == "X"]
        assert len(xs) == 2
        for e in xs:
            assert {"name", "ph", "pid", "tid", "ts", "dur"} <= set(e)
            assert isinstance(e["tid"], int)
        inst = [e for e in evs if e["ph"] == "i"]
        assert len(inst) == 1 and inst[0]["args"] == {"key": "x"}
        # µs conversion: the explicit-endpoint span is 4000ns = 4µs
        inflight = next(e for e in xs if e["name"] == "block_inflight")
        assert inflight["ts"] == 1.0 and inflight["dur"] == 4.0

    def test_disabled_tracer_is_a_shared_noop(self):
        t = Tracer(enabled=False)
        s1 = t.span("a", cat="stage")
        s2 = t.span("b", cat="stage", k=3)
        assert s1 is s2 is NULL_SPAN  # zero allocation on the off path
        with s1:
            pass
        t.instant("x")
        t.record("y", 0, 10)
        assert t.events() == []

    def test_capacity_bound_drops_and_counts(self):
        t = Tracer(capacity=2)
        for i in range(5):
            with t.span(f"s{i}"):
                pass
        assert len(t.events()) == 2
        assert t.dropped_events == 3
        assert t.to_chrome_trace()["otherData"]["dropped_events"] == 3

    def test_span_cost_micro_bound(self):
        """Backs the README overhead budget: a span must cost
        microseconds, not milliseconds — 10k spans under 0.5s is a
        50µs/span ceiling, ~100× above the measured cost but far below
        anything that could move a 3-5ms training step by 2%."""
        import time as _time
        t = Tracer(capacity=20_000)
        t0 = _time.perf_counter()
        for _ in range(10_000):
            with t.span("s", cat="dispatch"):
                pass
        assert _time.perf_counter() - t0 < 0.5
        assert len(t.events()) == 10_000


# ==========================================================================
# registry
# ==========================================================================
class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(4)
        reg.gauge("g").set(2.5)
        h = reg.histogram("h")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        assert reg.counter("c").value == 5
        assert reg.gauge("g").value == 2.5
        assert h.count == 3 and h.sum == 6.0 and h.mean == 2.0
        assert h.snapshot()["min"] == 1.0 and h.snapshot()["max"] == 3.0
        snap = reg.snapshot()
        json.dumps(snap)  # JSON-able
        assert snap["counters"]["c"] == 5
        assert snap["gauges"]["g"] == 2.5
        assert snap["histograms"]["h"]["p50"] == 2.0

    def test_type_conflict_raises(self):
        reg = MetricRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_thread_safety_under_concurrent_submit(self):
        reg = MetricRegistry()
        N, T = 2000, 8
        start = threading.Barrier(T)

        def worker():
            start.wait()
            for i in range(N):
                # get-or-create races on the same names by design
                reg.counter("shared/count").inc()
                reg.histogram("shared/lat").observe(i)
                reg.gauge("shared/g").set(i)

        threads = [threading.Thread(target=worker) for _ in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("shared/count").value == N * T
        h = reg.histogram("shared/lat")
        assert h.count == N * T
        assert h.sum == pytest.approx(T * N * (N - 1) / 2)

    def test_reservoir_percentile_contract(self):
        # the serving LatencyReservoir alias must keep its semantics
        from bigdl_tpu.serving import LatencyReservoir
        assert LatencyReservoir is Reservoir
        r = Reservoir(capacity=64)
        for v in range(1, 101):  # window keeps the most recent 64
            r.record(v / 1000.0)
        p = r.percentiles()
        assert set(p) == {"p50", "p95", "p99", "mean", "max"}
        assert p["p50"] <= p["p95"] <= p["p99"] <= p["max"] == 0.1
        assert r.count == 100


# ==========================================================================
# Metrics veneer back-compat
# ==========================================================================
class TestMetricsBackCompat:
    def test_summary_format_unchanged(self):
        m = Metrics()
        m.add("computing", 0.5)
        m.add("computing", 1.5)
        m.add("data", 0.25)
        assert m.summary() == (
            "computing: sum=2.0000 mean=1.0000 n=2\n"
            "data: sum=0.2500 mean=0.2500 n=1")
        assert m.value("computing") == 2.0
        assert m.mean("computing") == 1.0
        assert m.value("absent") == 0.0 and m.mean("absent") == 0.0
        m.reset()
        assert m.summary() == ""

    def test_time_context_manager(self):
        m = Metrics()
        with m.time("phase"):
            pass
        assert m.value("phase") > 0.0
        assert m.registry.histogram("phase").count == 1

    def test_shared_registry(self):
        reg = MetricRegistry()
        m = Metrics(registry=reg)
        m.add("x", 1.0)
        assert reg.histogram("x").count == 1

    def test_reset_clears_only_owned_names_on_shared_registry(self):
        """reset() must not wipe the watchdog metrics sharing the
        registry — a blanket registry.reset() would orphan the counter
        objects the watchdogs cache, silently losing every later
        increment from the snapshot."""
        reg = MetricRegistry()
        counter = reg.counter("telemetry/recompiles")  # watchdog-cached
        reg.gauge("driver/device_wait_fraction").set(0.5)
        m = Metrics(registry=reg)
        m.add("data", 1.0)
        m.reset()
        assert m.summary() == ""
        # foreign metrics survive, and the cached counter object is
        # STILL the registered one (no orphaning)
        assert reg.get("telemetry/recompiles") is counter
        counter.inc()
        assert reg.snapshot()["counters"]["telemetry/recompiles"] == 1
        assert reg.gauge("driver/device_wait_fraction").value == 0.5


# ==========================================================================
# serving: per-bucket latency reservoirs
# ==========================================================================
class TestServingPerBucketLatency:
    def test_snapshot_keys_by_bucket(self):
        from bigdl_tpu.serving.metrics import ServingMetrics
        sm = ServingMetrics()
        sm.record_done(1, 0.001, bucket=1)
        sm.record_done(4, 0.004, bucket=4)
        sm.record_done(3, 0.005, bucket=4)
        snap = sm.snapshot()
        assert set(snap["latency_ms_by_bucket"]) == {1, 4}
        assert snap["latency_ms_by_bucket"][1]["p50"] == 1.0
        # global window still sees every completion
        assert snap["latency_ms"]["max"] == 5.0

    def test_inference_service_stats_expose_buckets(self):
        from bigdl_tpu.serving import InferenceService
        model = nn.Sequential(nn.Linear(4, 3), nn.SoftMax())
        model.initialize(rng=0)
        svc = InferenceService(model, input_spec=((4,), np.float32),
                               max_batch_size=2, batch_timeout_ms=0.0,
                               name="bucketed")
        try:
            svc.predict(np.zeros((1, 4), np.float32))
            svc.predict(np.zeros((2, 4), np.float32))
            stats = svc.stats()
            by = stats["latency_ms_by_bucket"]
            assert by is not None and set(by) <= {1, 2}
            assert 1 in by and 2 in by
            for pct in by.values():
                assert {"p50", "p95", "p99"} <= set(pct)
        finally:
            svc.stop()


# ==========================================================================
# watchdogs
# ==========================================================================
class TestRecompileWatchdog:
    def test_flags_shape_churn_loop(self):
        reg, tr = MetricRegistry(), Tracer()
        wd = RecompileWatchdog(reg, tr)
        f = jax.jit(lambda x: x * 2)
        for n in (1, 2, 3, 4):  # seeded shape churn: retrace per shape
            f(np.zeros((n,), np.float32))
            wd.observe("step", jit_cache_size(f))
        assert wd.recompile_count == 3  # first compile is the baseline
        assert not wd.silent
        assert reg.counter("telemetry/recompiles").value == 3
        assert sum(1 for e in tr.events() if e[1] == "recompile") == 3

    def test_silent_on_aot_warmed_serving_path(self):
        from bigdl_tpu.serving import InferenceService
        model = nn.Sequential(nn.Linear(4, 3), nn.SoftMax())
        model.initialize(rng=0)
        svc = InferenceService(model, input_spec=((4,), np.float32),
                               max_batch_size=4, batch_timeout_ms=0.0,
                               name="warmed")
        wd = RecompileWatchdog()
        try:
            wd.observe("svc", svc.compile_count)  # post-warmup baseline
            rng = np.random.default_rng(0)
            for n in (1, 2, 3, 4, 1, 3):  # mixed sizes hit warm buckets
                svc.predict(rng.normal(0, 1, (n, 4)).astype(np.float32))
                assert not wd.observe("svc", svc.compile_count)
        finally:
            svc.stop()
        assert wd.silent and wd.recompile_count == 0

    def test_none_cache_size_is_noop(self):
        wd = RecompileWatchdog()
        assert wd.observe("k", None) is False
        assert jit_cache_size(lambda x: x) is None  # not a jit wrapper


class TestStallDetector:
    def test_starvation_flagged_and_fractions_sum(self):
        reg = MetricRegistry()
        det = StallDetector(reg, warm_blocks=0)
        # healthy pipelined block: device wait absorbs nearly everything
        det.record_block(stage_s=0.01, dispatch_s=0.001, wait_s=0.2,
                         replay_s=0.002)
        assert det.starvation_count == 0
        # starved block: staging dominates, device wait ~zero
        for _ in range(3):
            det.record_block(stage_s=0.2, dispatch_s=0.001, wait_s=0.001,
                             replay_s=0.001)
        assert det.starvation_count == 3
        fr = det.fractions()
        assert sum(fr.values()) == pytest.approx(1.0)
        assert reg.gauge("driver/host_stage_fraction").value == \
            pytest.approx(fr["stage"])

    def test_dispatch_sync_stall_flagged_but_not_for_compiles(self):
        reg = MetricRegistry()
        det = StallDetector(reg, warm_blocks=0, dispatch_stall_ms=50.0)
        det.record_block(0.0, 0.2, 0.0, 0.0, first_compile=True)
        assert det.sync_stall_count == 0  # planned compile, not a stall
        det.record_block(0.0, 0.2, 0.0, 0.0)
        assert det.sync_stall_count == 1

    def test_warm_blocks_withhold_verdicts(self):
        det = StallDetector(MetricRegistry(), warm_blocks=2)
        for _ in range(2):
            det.record_block(0.5, 0.2, 0.0, 0.0)
        assert det.starvation_count == 0 and det.sync_stall_count == 0

    def test_slow_step_args_is_not_stager_starvation(self):
        """One boundary for "stage": the detector's staging time is the
        time inside ``stager.take`` (the tracer's ``stage`` category),
        not the whole of ``stage_next()``.  A driver whose step
        arguments are slow (a schedule that takes 5 ms a call) over a
        fast input pipeline is not starved by its stager."""

        class SlowScheduleSGD(optim.SGD):
            def current_lr(self, *a, **kw):
                time.sleep(0.005)
                return super().current_lr(*a, **kw)

        class SlowSummary(RecordingSummary):
            # a replay of some length, so that the block's host time is
            # not made of the (fast) take alone
            def add_train_step(self, *a):
                time.sleep(0.002)
                super().add_train_step(*a)

        opt = (LocalOptimizer(small_mlp(), mnist_pipeline(256, 32),
                              nn.ClassNLLCriterion())
               .set_optim_method(SlowScheduleSGD(1e-2))
               .set_train_summary(SlowSummary())
               .set_steps_per_dispatch(4)
               .set_end_when(optim.max_iteration(24))
               .set_telemetry(True))
        opt.optimize()
        tel = opt._telemetry
        totals = {}
        for ph, _n, cat, _t0, dur_ns, *_rest in tel.tracer.events():
            if ph == "X":
                totals[cat] = totals.get(cat, 0.0) + dur_ns / 1e9
        # the premise: step_args dwarfs the take
        assert totals["step_args"] > 2 * totals["stage"]
        assert tel.stalls.blocks_observed == 6
        assert tel.stalls.starvation_count == 0
        assert not [e for e in tel.tracer.events()
                    if e[1] == "stager_starvation"]
        # gauge and tracer describe one interval: the detector's stage
        # seconds are the tracer's ``stage`` seconds (two clock reads
        # apart), nowhere near ``stage_next``'s
        stage_s = tel.stalls._totals["stage"]
        assert stage_s == pytest.approx(totals["stage"], rel=0.1)
        assert stage_s < 0.5 * totals["stage_next"]


class TestMemoryWatermark:
    def test_degrades_silently_without_backend_stats(self):
        reg = MetricRegistry()
        mw = MemoryWatermark(reg)

        class NoStats:
            def memory_stats(self):
                return None

        assert mw.observe(NoStats()) is None
        assert mw.available is False
        assert reg.names() == []

    def test_gauges_when_stats_present(self):
        reg = MetricRegistry()
        mw = MemoryWatermark(reg)

        class WithStats:
            def memory_stats(self):
                return {"bytes_in_use": 1024, "peak_bytes_in_use": 4096}

        assert mw.observe(WithStats())["bytes_in_use"] == 1024
        assert mw.available is True
        assert reg.gauge("device/bytes_in_use").value == 1024
        assert reg.gauge("device/peak_bytes_in_use").value == 4096


# ==========================================================================
# the inertness gate + end-to-end trace
# ==========================================================================
def mnist_pipeline(n, batch, seed=0):
    imgs, labels = mnist.synthetic_mnist(n, seed=seed)
    samples = mnist.to_samples(imgs, labels)
    ds = (DataSet.array(samples)
          >> image.BytesToGreyImg()
          >> image.GreyImgNormalizer(mnist.TRAIN_MEAN, mnist.TRAIN_STD))
    return ds >> SampleToMiniBatch(batch)


def small_mlp():
    return (nn.Sequential()
            .add(nn.Reshape((784,)))
            .add(nn.Linear(784, 32)).add(nn.ReLU())
            .add(nn.Linear(32, 10)).add(nn.LogSoftMax()))


class RecordingSummary:
    def __init__(self):
        self.rows = []
        self.scalars = []

    def add_train_step(self, step, loss, lr, throughput):
        self.rows.append((step, loss, lr))

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, value, step))

    def trigger_for(self, name):
        return None

    @property
    def losses(self):
        return np.array([l for _, l, _ in self.rows])


def run_counted(k, telemetry, trace_path=None, iters=11, n=256, batch=32):
    """One small training run with a dispatch-counting wrapper around
    the REAL block fns (the test_fused_step budget discipline)."""
    calls = {"n": 0}
    rec = RecordingSummary()
    opt = (LocalOptimizer(small_mlp(), mnist_pipeline(n, batch),
                          nn.ClassNLLCriterion())
           .set_optim_method(optim.Adam(1e-3))
           .set_train_summary(rec)
           .set_steps_per_dispatch(k)
           .set_end_when(optim.max_iteration(iters)))
    opt.set_telemetry(telemetry, trace_path=trace_path)
    orig = opt._build_block_fn

    def counting_build(grad_fn, kk):
        fn = orig(grad_fn, kk)

        def wrapped(*a, **kw):
            calls["n"] += 1
            return fn(*a, **kw)

        # expose the real jit underneath so the recompile watchdog's
        # cache-size probe still sees it through the wrapper
        wrapped._cache_size = getattr(fn, "_cache_size", None)
        return wrapped

    opt._build_block_fn = counting_build
    opt.optimize()
    return rec, opt, calls["n"]


class TestTelemetryInert:
    @pytest.mark.parametrize("k", [1, 4])
    def test_bitwise_identical_loss_and_dispatch_count(self, k, tmp_path):
        """THE acceptance gate: telemetry on changes NOTHING observable
        about training — per-step losses bitwise equal, same number of
        jit dispatches — while still emitting a valid trace.

        Round 2 extends the gate to the full new surface: with the
        admin plane off, the flight recorder off and request tracing
        off (all defaults), the run allocates NO admin server, NO
        flight recorder, NO request contexts and ZERO extra threads —
        and the loss sequence/dispatch count remain the off-path
        numbers."""
        from bigdl_tpu.telemetry import admin as admin_mod
        from bigdl_tpu.telemetry import flight as flight_mod
        threads_before = {t.ident for t in threading.enumerate()}
        rec_off, opt_off, n_off = run_counted(k, telemetry=False)
        # the new observability surface stayed entirely un-allocated
        assert admin_mod.current() is None
        assert flight_mod.current() is None
        assert opt_off._flight is None
        surviving = [t for t in threading.enumerate()
                     if t.ident not in threads_before and t.is_alive()]
        assert not [t for t in surviving
                    if t.name == "bigdl-tpu-admin"], surviving
        # zero extra threads: whatever transient helpers ran (stager
        # producer), nothing new outlives the run
        assert not surviving, surviving
        trace = str(tmp_path / f"trace_k{k}.json")
        rec_on, opt_on, n_on = run_counted(k, telemetry=True,
                                           trace_path=trace)
        np.testing.assert_array_equal(rec_off.losses, rec_on.losses)
        assert n_off == n_on
        assert opt_off._dispatch_count == opt_on._dispatch_count
        budget = math.ceil(11 / k) + 2
        assert n_on <= budget
        # telemetry-off leaves no telemetry state behind
        assert opt_off.telemetry_snapshot() is None
        assert opt_on.telemetry_snapshot() is not None
        # ... and the enabled run produced a trace the reporter can
        # summarize with phase shares that close to ~1
        report = trace_report.summarize(trace_report.load_trace(trace))
        assert report["span_count"] > 0
        assert sum(report["phase_share"].values()) == pytest.approx(
            1.0, abs=0.02)
        for cat in ("stage", "dispatch", "device_wait", "replay"):
            assert cat in report["phase_seconds"], report["phase_seconds"]

    def test_no_steady_state_recompiles_in_driver(self, tmp_path):
        """The fused driver's block fns compile once per block length —
        the recompile watchdog must stay silent across a multi-epoch
        run (the negative control for the runtime GL106 gate)."""
        _, opt, _ = run_counted(4, telemetry=True,
                                trace_path=str(tmp_path / "t.json"),
                                iters=16)
        snap = opt.telemetry_snapshot()
        assert snap["watchdogs"]["recompile_events"] == []
        assert snap["watchdogs"]["blocks_observed"] > 0

    def test_gauges_mirrored_into_train_summary(self, tmp_path):
        rec, opt, _ = run_counted(4, telemetry=True,
                                  trace_path=str(tmp_path / "t.json"))
        tags = {t for t, _, _ in rec.scalars}
        assert "Telemetry/driver/device_wait_fraction" in tags
        assert "Telemetry/driver/host_stage_fraction" in tags

    def test_off_run_writes_no_trace(self, tmp_path):
        trace = str(tmp_path / "never.json")
        rec, opt, _ = run_counted(1, telemetry=False, trace_path=trace)
        assert not os.path.exists(trace)

    def test_set_telemetry_false_actually_disables_on_reuse(self,
                                                            tmp_path):
        """Toggling off between runs on the SAME optimizer must drop
        the stale DriverTelemetry — _tel_span reads self._telemetry, so
        a leftover bundle would keep recording through an 'off' run."""
        rec = RecordingSummary()
        opt = (LocalOptimizer(small_mlp(), mnist_pipeline(128, 32),
                              nn.ClassNLLCriterion())
               .set_optim_method(optim.Adam(1e-3))
               .set_train_summary(rec)
               .set_telemetry(True,
                              trace_path=str(tmp_path / "t.json"))
               .set_end_when(optim.max_iteration(3)))
        opt.optimize()
        tel_first = opt._telemetry
        assert tel_first is not None
        events_after_on = len(tel_first.tracer.events())
        assert events_after_on > 0
        opt.set_telemetry(False)
        opt.set_end_when(optim.max_iteration(6))
        opt.optimize()
        assert opt._telemetry is None
        assert opt.telemetry_snapshot() is None
        # the old bundle stopped recording too
        assert len(tel_first.tracer.events()) == events_after_on


# ==========================================================================
# driver span coverage + the profiler mirror
# ==========================================================================
def wide_mlp():
    # wide enough that a step takes milliseconds on the CPU: blocks of
    # tens of milliseconds, beside which the loop's own bookkeeping
    # (tens of microseconds) cannot make the coverage floor unsteady
    return (nn.Sequential()
            .add(nn.Reshape((784,)))
            .add(nn.Linear(784, 1024)).add(nn.ReLU())
            .add(nn.Linear(1024, 1024)).add(nn.ReLU())
            .add(nn.Linear(1024, 10)).add(nn.LogSoftMax()))


def _spans(tracer):
    """The driver thread's spans as dicts, the virtual tracks (the
    device's blocks, the assembler thread's batches) apart."""
    host, device, assembler = [], [], []
    for ph, name, cat, t0, dur, tid, args, _flow in tracer.events():
        if ph != "X":
            continue
        row = dict(name=name, cat=cat, t0=t0, t1=t0 + dur, tid=tid,
                   args=args or {})
        {"device": device, "assembler": assembler}.get(tid, host).append(row)
    return host, device, assembler


def _inside(child, parent):
    return (child["tid"] == parent["tid"] and parent["t0"] <= child["t0"]
            and child["t1"] <= parent["t1"])


class TestDriverSpanCoverage:
    PARENT_OF = {"plan": "stage_next", "host_stack": "stage_next",
                 "h2d_stage": "stage_next", "step_args": "stage_next",
                 "buffer_return": "host_stack",
                 "batch_pull": "host_stack", "block_stack": "host_stack",
                 "validation": "replay"}

    @pytest.mark.parametrize("assembler", [SampleToMiniBatch,
                                           MTSampleToMiniBatch],
                             ids=["inline", "threaded"])
    @pytest.mark.parametrize("k", [1, 4])
    def test_top_level_spans_tile_the_driver_loop(self, k, assembler):
        imgs, labels = mnist.synthetic_mnist(1024, seed=0)
        base = (DataSet.array(mnist.to_samples(imgs, labels))
                >> image.BytesToGreyImg()
                >> image.GreyImgNormalizer(mnist.TRAIN_MEAN,
                                           mnist.TRAIN_STD))
        opt = (LocalOptimizer(wide_mlp(), base >> assembler(256),
                              nn.ClassNLLCriterion())
               .set_optim_method(optim.SGD(1e-2))
               .set_steps_per_dispatch(k)
               .set_validation(optim.several_iteration(8),
                               base >> SampleToMiniBatch(256),
                               [optim.Top1Accuracy()])
               .set_end_when(optim.max_iteration(16))
               .set_telemetry(True))
        opt.optimize()
        tracer = opt._telemetry.tracer
        assert tracer.dropped_events == 0
        host, device, assembled = _spans(tracer)
        n_blocks = opt._dispatch_count
        assert n_blocks == 16 // k

        # -- one block number from staging to replay
        for cat in TOP_LEVEL_CATS:
            rows = [s for s in host if s["cat"] == cat]
            assert [s["name"] for s in rows] == [cat] * n_blocks
            assert sorted(s["args"]["block"] for s in rows) == \
                list(range(n_blocks)), cat
        assert sorted(s["args"]["block"] for s in device
                      if s["name"] == "block_inflight") == \
            list(range(n_blocks))
        driver_tid = {s["tid"] for s in host
                      if s["cat"] in TOP_LEVEL_CATS}
        assert len(driver_tid) == 1  # all four on the driver's thread
        by_block = {(s["cat"], s["args"]["block"]): s for s in host
                    if s["cat"] in TOP_LEVEL_CATS}
        for b in range(n_blocks):
            # staged (one block ahead), dispatched, waited for, replayed
            assert (by_block["stage_next", b]["t1"]
                    <= by_block["dispatch", b]["t0"]
                    <= by_block["device_wait", b]["t0"]
                    <= by_block["replay", b]["t0"])
        for s in host:
            if s["name"] == "validation":
                assert any(p["name"] == "replay" and _inside(s, p)
                           and p["args"]["block"] == s["args"]["block"]
                           for p in host)

        # -- every child lies inside its parent
        seen = set()
        for s in host:
            parent = self.PARENT_OF.get(s["name"])
            if parent is None:
                assert s["cat"] in TOP_LEVEL_CATS, s
                continue
            seen.add(s["name"])
            assert any(p["name"] == parent and _inside(s, p)
                       for p in host), s
        assert seen == set(self.PARENT_OF)
        pulls = [s for s in host if s["name"] == "batch_pull"]
        assert len(pulls) >= 16
        assert all(0 <= s["args"]["n"] < k for s in pulls)
        for s in host:
            if s["name"] in ("block_stack", "h2d_stage"):
                # k (or fewer) batches of 256 f32 images and i32 labels
                assert s["args"]["bytes"] % (256 * (784 * 4 + 4)) == 0

        # -- the assembler thread's work on every staged batch, in no
        # span of the driver: a track of its own (the inline assembler
        # works inside batch_pull and has none)
        if assembler is MTSampleToMiniBatch:
            assert len(assembled) == 16
            for s in assembled:
                assert (s["name"], s["cat"]) == ("assemble",
                                                 "batch_assemble")
                assert s["cat"] in OFF_DRIVER_CATS
                assert s["args"]["bytes"] == 256 * (784 * 4 + 4)
                # the CPU backend may alias a placed array: no buffer
                # ever comes back
                assert s["args"]["recycled"] is False
                assert s["t0"] < s["t1"]
            counters = opt._telemetry.registry.snapshot()["counters"]
            assert counters["input/buffers_allocated"] == 16
            assert counters["input/buffers_recycled"] == 0
        else:
            assert assembled == []

        # -- the four top-level categories cover the driver's time
        tops = sorted((s for s in host if s["cat"] in TOP_LEVEL_CATS),
                      key=lambda s: s["t0"])
        assert tops[0]["name"] == "stage_next"
        assert tops[-1]["name"] == "replay"
        for a, b in zip(tops, tops[1:]):
            assert a["t1"] <= b["t0"]  # they tile, never overlap
        covered = sum(s["t1"] - s["t0"] for s in tops)
        wall = tops[-1]["t1"] - tops[0]["t0"]
        assert wall / n_blocks > 5e6  # blocks of milliseconds (ns)
        assert covered / wall >= 0.90, (covered, wall)


def _bigdl_lines(log_dir):
    """``{line name: [(name, start_ns, end_ns, stats)]}`` of the
    ``bigdl:`` events on the host plane of a capture."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return {}
    out = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("bigdl:")]
            if evs:
                out[(i, line.name)] = evs
    return out


class TestProfilerMirror:
    @staticmethod
    def _drive(tracer):
        with tracer.span("stage_next", cat="stage_next", block=3):
            time.sleep(0.002)
            with tracer.span("host_stack", cat="stage", k=4,
                             label="x", skipped=[1, 2]):
                time.sleep(0.002)
            time.sleep(0.002)

        def other():
            with tracer.span("wire_request", cat="serving", rows=2):
                time.sleep(0.002)

        th = threading.Thread(target=other, name="second")
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
        tracer.instant("recompile", key="k")
        tracer.record("block_inflight", 0, 10, cat="pipeline",
                      track="device")

    def test_spans_land_in_a_profiler_capture(self, tmp_path):
        tracer = Tracer()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # what the train runner sets
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            self._drive(tracer)
        finally:
            jax.profiler.stop_trace()
        lines = _bigdl_lines(str(tmp_path))
        assert len(lines) == 2  # one line a thread
        events = {name: (t0, t1, stats, line)
                  for line, evs in lines.items()
                  for name, t0, t1, stats in evs}
        # spans only: instants, explicit records and flows have no live
        # scope and are not mirrored
        assert set(events) == {"bigdl:stage_next:stage_next",
                               "bigdl:stage:host_stack",
                               "bigdl:serving:wire_request"}
        p0, p1, pstats, pline = events["bigdl:stage_next:stage_next"]
        c0, c1, cstats, cline = events["bigdl:stage:host_stack"]
        assert pline == cline and p0 <= c0 and c1 <= p1  # nested
        assert c1 - c0 >= 2e6 and p1 - p0 >= 6e6
        assert pstats == {"block": 3}
        assert cstats == {"k": 4, "label": "x"}  # scalars only
        _w0, _w1, wstats, wline = events["bigdl:serving:wire_request"]
        assert wline != pline and wstats == {"rows": 2}
        # the Chrome-trace side is what it was: same spans, all args
        by = {e[1]: e for e in tracer.events()}
        assert by["host_stack"][6] == {"k": 4, "label": "x",
                                      "skipped": [1, 2]}

    def test_no_session_no_capture_and_off_is_off(self, tmp_path):
        tracer = Tracer()
        self._drive(tracer)  # no profiler session: annotations are inert
        assert _bigdl_lines(str(tmp_path)) == {}
        assert len([e for e in tracer.events() if e[0] == "X"]) == 4
        off = Tracer(enabled=False)
        assert off.span("a", cat="stage", block=1) is NULL_SPAN
        assert off._annotation is None  # jax.profiler left alone
        # a tracer switched on after it was made mirrors from its
        # first span on
        off.enabled = True
        with off.span("a", cat="stage"):
            pass
        assert off._annotation is not None and len(off.events()) == 1


class TestConfigSurface:
    def test_config_fields_exist(self):
        from bigdl_tpu.utils.config import Config
        cfg = Config()
        assert cfg.telemetry_enabled is False
        assert cfg.telemetry_trace_path == ""
        assert cfg.telemetry_trace_capacity == 200_000
        # round 2 (admin plane / flight recorder / request tracing):
        # every new knob defaults to the provably-inert state
        assert cfg.admin_port == 0
        assert cfg.request_tracing is False
        assert cfg.flight_recorder_path == ""
        assert cfg.flight_recorder_capacity == 4096

    def test_round2_env_knobs(self, monkeypatch):
        from bigdl_tpu.utils.config import Config
        monkeypatch.setenv("BIGDL_TPU_ADMIN_PORT", "9187")
        monkeypatch.setenv("BIGDL_TPU_REQUEST_TRACING", "1")
        monkeypatch.setenv("BIGDL_TPU_FLIGHT_RECORDER_PATH",
                           "/tmp/fl.jsonl")
        cfg = Config.from_env()
        assert cfg.admin_port == 9187
        assert cfg.request_tracing is True
        assert cfg.flight_recorder_path == "/tmp/fl.jsonl"

    def test_env_alias(self, monkeypatch):
        from bigdl_tpu.utils.config import Config
        monkeypatch.setenv("BIGDL_TPU_TELEMETRY", "1")
        assert Config.from_env().telemetry_enabled is True
        # the explicit long form wins over the alias
        monkeypatch.setenv("BIGDL_TPU_TELEMETRY_ENABLED", "0")
        assert Config.from_env().telemetry_enabled is False

    def test_set_telemetry_builder(self):
        opt = LocalOptimizer(small_mlp(), mnist_pipeline(64, 32),
                             nn.ClassNLLCriterion())
        assert opt.telemetry_enabled is None  # resolve from config
        assert opt.set_telemetry(True, "x.json") is opt
        assert opt.telemetry_enabled is True
        assert opt.telemetry_trace_path == "x.json"


# ==========================================================================
# trace_report (fixture-driven)
# ==========================================================================
class TestTraceReport:
    FIXTURE = os.path.join(FIXTURES, "trace_pipeline.json")

    def test_fixture_summary_exact(self):
        report = trace_report.summarize(
            trace_report.load_trace(self.FIXTURE))
        assert report["wall_s"] == pytest.approx(1.0)
        share = report["phase_share"]
        # hand-built fixture, one block of the driver's loop: stage_next
        # .3 holding plan .01, the stage spans host_stack .12 (itself
        # holding buffer_return .005, batch_pull .05 and block_stack .04)
        # and h2d_stage .08,
        # and step_args .07; dispatch .1, wait .4, replay .1 with a
        # nested 40ms trigger span; other .1.  Self-time splits every
        # parent; the device-track pipeline span must NOT count
        assert share == {"stage_next": 0.02, "plan": 0.01, "stage": 0.105,
                         "buffer_return": 0.005,
                         "batch_pull": 0.05, "block_stack": 0.04,
                         "step_args": 0.07, "dispatch": 0.1,
                         "device_wait": 0.4, "replay": 0.06,
                         "trigger": 0.04, "other": 0.1}
        assert sum(share.values()) == pytest.approx(1.0)
        # every category of the fixture is one the tracer declares, and
        # the top-level four cover what "other" does not
        # the assembler's batch (250 ms on a track of its own) overlaps
        # the driver's time: reported beside the share, not in it
        assert report["off_driver_share"] == {"batch_assemble": 0.25}
        assert (set(share) - {"other"}) | set(report["off_driver_share"]) \
            == set(trace_report.PHASE_CATS)
        assert report["driver_coverage"] == 0.9
        # the stall picture takes whole spans: the stager held the
        # driver for .2, whoever worked inside
        assert report["stall"]["host_stage_fraction"] == 0.2
        assert report["stall"]["device_wait_fraction"] == 0.4
        assert report["watchdog_events"] == {"recompile": 2,
                                             "stager_starvation": 1}
        assert len(report["recompile_events"]) == 2
        top = report["top_spans"]
        assert top[0]["name"] == "device_wait"
        assert top[0]["total_ms"] == 400.0

    def test_cli_exit_codes(self, tmp_path, capsys):
        assert trace_report.main([self.FIXTURE]) == 0
        out = capsys.readouterr().out
        assert "phase share" in out and "device_wait" in out
        assert trace_report.main([self.FIXTURE, "--json"]) == 0
        json.loads(capsys.readouterr().out)  # valid JSON mode
        missing = str(tmp_path / "nope.json")
        assert trace_report.main([missing]) == 2
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        assert trace_report.main([str(bad)]) == 2

    def test_bare_event_list_accepted(self, tmp_path):
        events = json.load(open(self.FIXTURE))["traceEvents"]
        p = tmp_path / "bare.json"
        p.write_text(json.dumps(events))
        report = trace_report.summarize(trace_report.load_trace(str(p)))
        assert report["wall_s"] == pytest.approx(1.0)


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
