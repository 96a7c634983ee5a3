"""bigdl_tpu.resilience — fault injection, self-healing serving,
numeric-failure recovery.

The load-bearing gates (ISSUE 10 acceptance):

- **Bitwise inertness** (K ∈ {1, 4}): with ``fault_plan=None`` no
  injector object exists and with ``numeric_guard`` live over all-finite
  training the loss sequence, dispatch count and final params are
  bitwise-identical to the default run; serving through a ``ReplicaSet``
  with no injector is bitwise-equal to direct ``model.apply``.
- **Self-healing**: a replica whose batcher thread is killed
  mid-traffic (real subprocess) is quarantined, its accepted requests
  fail over with zero losses and zero wrong answers, and it re-admits
  after probation — all visible in the ``resilience/*`` counters.
- **Numeric recovery**: ``skip`` gates the poisoned update away on
  device and training continues; ``rollback`` restores the latest
  valid snapshot; ``abort`` raises at the exact iteration.

Event-driven where possible (staged ``start=False`` services, injected
clocks for health/breaker state machines); the only polls are the ones
the production code itself documents as unavoidable (dead threads
cannot notify).
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.dataset.prefetch import (DeviceBlockStager,
                                        MTSampleToMiniBatch)
from bigdl_tpu.resilience import (CircuitBreaker, FaultInjector,
                                  HealthPolicy, NonFiniteStepError,
                                  ReplicaHealth, ReplicaSet,
                                  parse_fault_plan)
from bigdl_tpu.resilience.faults import (InjectedFault,
                                         ReplicaDeathFault)
from bigdl_tpu.resilience.health import (ADMIT, PROBE, REFUSE,
                                         DEGRADED, HEALTHY, QUARANTINED)
from bigdl_tpu.serving import (DeadlineExceeded, InferenceService,
                               ModelRegistry, ServiceOverloaded)
from bigdl_tpu.telemetry.registry import MetricRegistry
from bigdl_tpu.utils.config import configure, reset_config

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CHILD = os.path.join(HERE, "resil_serve_child.py")


def make_model(din=16, dout=4):
    return nn.Sequential(nn.Linear(din, 32), nn.ReLU(),
                         nn.Linear(32, dout), nn.SoftMax()).initialize(0)


SPEC16 = ((16,), np.float32)


def rows(rng, n, din=16):
    return rng.normal(0, 1, (n, din)).astype(np.float32)


@pytest.fixture(autouse=True)
def _clean_config():
    reset_config()
    yield
    reset_config()


# ===========================================================================
class TestFaultPlanGrammar:
    def test_full_grammar_parses_and_describes(self):
        plan = ("dispatch_error@at=3,target=1;"
                "dispatch_delay@ms=5.0,every=2,where=driver;"
                "replica_death@after=10,count=1;"
                "corrupt_batch@at=7;nonfinite_grads@p=0.5,until=20")
        clauses = parse_fault_plan(plan)
        assert [c.kind for c in clauses] == [
            "dispatch_error", "dispatch_delay", "replica_death",
            "corrupt_batch", "nonfinite_grads"]
        assert clauses[0].at == 3 and clauses[0].target == 1
        assert clauses[1].ms == 5.0 and clauses[1].where == "driver"
        assert clauses[2].after == 10 and clauses[2].count == 1
        # batch kinds always live in the driver
        assert clauses[3].where == "driver"
        # describe() round-trips through the parser
        redesc = parse_fault_plan(
            "; ".join(c.describe() for c in clauses))
        assert [c.describe() for c in redesc] == \
            [c.describe() for c in clauses]

    def test_empty_and_whitespace_plans_are_no_clauses(self):
        assert parse_fault_plan("") == []
        assert parse_fault_plan("  ;  ; ") == []

    @pytest.mark.parametrize("plan", [
        "exploding_gradient_storm",          # unknown kind
        "dispatch_error@frequency=3",        # unknown key
        "dispatch_error@at",                 # missing =
        "dispatch_error@p=1.5",              # p out of range
        "dispatch_error@where=everywhere",   # bad where
        "dispatch_delay@every=0",            # every < 1
    ])
    def test_malformed_plans_fail_loudly(self, plan):
        with pytest.raises(ValueError):
            parse_fault_plan(plan)

    def test_from_config_returns_none_for_empty_plan(self):
        # the provably-inert state: no injector OBJECT exists, so every
        # call site's `injector is not None` guard keeps the disabled
        # path byte-identical
        assert FaultInjector.from_config() is None
        configure(fault_plan="dispatch_error@at=0")
        try:
            inj = FaultInjector.from_config()
            assert inj is not None and len(inj.clauses) == 1
        finally:
            reset_config()

    def test_windows_and_budget(self):
        inj = FaultInjector("dispatch_error@after=2,until=5,count=2,"
                            "where=driver")
        fired = []
        for i in range(8):
            try:
                inj.driver_dispatch(i)
            except InjectedFault:
                fired.append(i)
        # window [2, 5) admits 2,3,4; the count=2 budget stops at two
        assert fired == [2, 3]

    def test_target_scoping(self):
        inj = FaultInjector("dispatch_error@target=1")
        inj.serving_dispatch(0, replica=0)  # wrong replica: no fire
        with pytest.raises(InjectedFault):
            inj.serving_dispatch(0, replica=1)

    def test_probabilistic_clause_is_deterministic(self):
        plan = "dispatch_error@p=0.5,where=driver"

        def firing_set(seed):
            inj = FaultInjector(plan, seed=seed)
            out = set()
            for i in range(64):
                try:
                    inj.driver_dispatch(i)
                except InjectedFault:
                    out.add(i)
            return out

        a, b = firing_set(7), firing_set(7)
        assert a == b                       # replayable
        assert 8 < len(a) < 56              # actually probabilistic
        assert firing_set(8) != a           # seed matters

    def test_replica_death_is_base_exception(self):
        # must ESCAPE the dispatch error handler (Exception-scoped) so
        # it strands futures exactly like a real thread crash
        assert not issubclass(ReplicaDeathFault, Exception)
        inj = FaultInjector("replica_death@at=0")
        with pytest.raises(ReplicaDeathFault):
            inj.serving_dispatch(0, replica=None)

    def test_registry_counts_injected_faults(self):
        reg = MetricRegistry()
        inj = FaultInjector("dispatch_delay@ms=0.1,count=2",
                            registry=reg)
        for i in range(4):
            inj.serving_dispatch(i)
        assert reg.counter(
            "resilience/fault_dispatch_delay").value == 2


# ===========================================================================
class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestReplicaHealth:
    def test_degrade_and_recover(self):
        clock = _Clock()
        h = ReplicaHealth(0, HealthPolicy(), clock=clock)
        assert h.state == HEALTHY
        h.record_failure()
        assert h.state == DEGRADED
        h.record_success()
        assert h.state == HEALTHY

    def test_quarantine_probe_readmit_cycle(self):
        clock = _Clock()
        reg = MetricRegistry()
        h = ReplicaHealth(0, HealthPolicy(probe_backoff_s=1.0,
                                          probe_jitter=0.0),
                          registry=reg, clock=clock)
        for _ in range(3):
            h.record_failure()
        assert h.state == QUARANTINED
        assert h.admit() == REFUSE          # probation not yet due
        clock.t = 1.5
        assert h.admit() == PROBE           # exactly one probe
        assert h.admit() == REFUSE          # while the probe is in flight
        h.record_success(probe=True)
        assert h.state == HEALTHY
        assert h.admit() == ADMIT
        assert reg.counter("resilience/quarantines").value == 1
        assert reg.counter("resilience/probes").value == 1
        assert reg.counter("resilience/readmissions").value == 1

    def test_failed_probe_doubles_backoff(self):
        clock = _Clock()
        h = ReplicaHealth(0, HealthPolicy(probe_backoff_s=1.0,
                                          probe_jitter=0.0),
                          clock=clock)
        h.mark_dead()
        assert h.state == QUARANTINED
        first_wait = h.next_probe_in()
        assert first_wait == pytest.approx(1.0)
        clock.t = 1.0
        assert h.admit() == PROBE
        h.record_failure(probe=True)
        # the next window uses the doubled backoff
        assert h.next_probe_in() == pytest.approx(2.0)
        # a probe success resets the ladder
        clock.t = 3.0
        assert h.admit() == PROBE
        h.record_success(probe=True)
        h.mark_dead()
        assert h.next_probe_in() == pytest.approx(1.0)

    def test_jitter_is_deterministic_per_replica(self):
        mk = lambda ix: ReplicaHealth(  # noqa: E731
            ix, HealthPolicy(probe_backoff_s=1.0, probe_jitter=0.5,
                             seed=3), clock=_Clock())
        a, b, c = mk(0), mk(0), mk(1)
        for h in (a, b, c):
            h.mark_dead()
        assert a.next_probe_in() == b.next_probe_in()   # replayable
        assert a.next_probe_in() != c.next_probe_in()   # decorrelated

    def test_stale_nonprobe_success_does_not_readmit(self):
        clock = _Clock()
        h = ReplicaHealth(0, HealthPolicy(), clock=clock)
        h.mark_dead()
        h.record_success(probe=False)  # late completion from pre-death
        assert h.state == QUARANTINED

    def test_stale_nonprobe_failures_do_not_inflate_backoff(self):
        # regression: a wedge with N requests in flight drains N stale
        # failures into the quarantined replica; they must not
        # reschedule the probe window or double the backoff — one
        # incident is one piece of evidence
        clock = _Clock()
        h = ReplicaHealth(0, HealthPolicy(probe_backoff_s=0.5,
                                          probe_jitter=0.0),
                          clock=clock)
        h.mark_dead()
        first = h.next_probe_in()
        for _ in range(8):
            h.record_failure(probe=False)  # stranded-request drain
        assert h.next_probe_in() == pytest.approx(first)
        clock.t = first
        assert h.admit() == PROBE  # probation unchanged at 0.5s


class TestCircuitBreaker:
    def test_trip_halfopen_retrip_close(self):
        clock = _Clock()
        reg = MetricRegistry()
        brk = CircuitBreaker(trip_after=3, cooldown_s=10.0,
                             registry=reg, clock=clock)
        for _ in range(2):
            brk.record_failure()
        assert brk.allow()
        brk.record_failure()                 # third: trips
        assert not brk.allow()
        assert reg.counter("resilience/breaker_trips").value == 1
        clock.t = 10.0
        assert brk.allow()                   # half-open
        brk.record_failure()                 # failed trial: re-trip,
        assert not brk.allow()               # cooldown doubled
        clock.t = 25.0
        assert not brk.allow()               # 20s cooldown from t=10
        clock.t = 30.0
        assert brk.allow()
        brk.record_success()                 # closes + resets
        assert brk.allow()
        assert brk.snapshot()["cooldown_s"] == 10.0

    def test_overload_is_not_a_poison_signal(self):
        # contract: ModelRegistry must NOT record ServiceOverloaded /
        # ServiceClosed outcomes into the breaker
        reg = ModelRegistry(breaker_trip_after=1)
        svc_outcomes = reg.record_outcome
        brk = CircuitBreaker(trip_after=1)
        svc_outcomes(brk, ServiceOverloaded(5, 5, "m"))
        assert brk.allow()
        svc_outcomes(brk, RuntimeError("boom"))
        assert not brk.allow()


class TestRegistryBreakerFallback:
    def _registry_with_two_versions(self):
        metrics = MetricRegistry()
        reg = ModelRegistry(breaker_trip_after=2,
                            breaker_cooldown_s=3600.0, registry=metrics)
        model = make_model()
        reg.deploy("m", model, version=1, input_spec=SPEC16,
                   max_batch_size=4)
        reg.deploy("m", model, version=2, input_spec=SPEC16,
                   max_batch_size=4)
        return reg, metrics

    def test_poisoned_latest_falls_back_to_previous(self):
        reg, metrics = self._registry_with_two_versions()
        rng = np.random.default_rng(0)
        x = rows(rng, 2)
        v2 = reg.get("m", 2)
        expected = np.asarray(reg.get("m", 1).predict(x, timeout=60))
        # poison v2: every request dies at its future
        poisoned = lambda *a, **k: (_ for _ in ()).throw(  # noqa: E731
            RuntimeError("poisoned deploy"))
        v2.predict = poisoned
        for _ in range(2):
            with pytest.raises(RuntimeError):
                reg.predict("m", x, timeout=60)
        assert reg.breaker_state("m", 2)["open"]
        # latest-wins now routes around the tripped version
        out = reg.predict("m", x, timeout=60)
        np.testing.assert_array_equal(np.asarray(out), expected)
        assert metrics.counter(
            "resilience/breaker_fallbacks").value >= 1
        # pinned requests bypass the breaker: the caller asked for v2,
        # they get its errors
        with pytest.raises(RuntimeError):
            reg.predict("m", x, version=2, timeout=60)
        reg.stop_all()

    def test_cancelled_future_is_no_breaker_outcome(self):
        # regression: a cancelled submit() future used to record a
        # breaker SUCCESS, resetting a poisoned deploy's failure streak
        reg, _ = self._registry_with_two_versions()
        brk = reg._breakers[("m", 2)]
        brk.record_failure()
        fut = reg.submit("m", rows(np.random.default_rng(2), 1),
                         version=2)
        fut.cancel()  # may or may not win vs the batcher — both legal
        time.sleep(0.05)  # let the done-callback run
        if fut.cancelled():
            assert brk.snapshot()["consecutive_failures"] == 1
        reg.stop_all()

    def test_all_breakers_open_serves_newest_anyway(self):
        reg, _ = self._registry_with_two_versions()
        rng = np.random.default_rng(1)
        x = rows(rng, 1)
        for v in (1, 2):
            brk = reg._breakers[("m", v)]
            brk.record_failure()
            brk.record_failure()
            assert not brk.allow()
        # serving a maybe-poisoned model beats serving nothing
        out = reg.predict("m", x, timeout=60)
        assert np.asarray(out).shape == (1, 4)
        reg.stop_all()


# ===========================================================================
class TestDeadlines:
    def test_expired_before_submit_never_queues(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, start=False)
        fut = svc.submit(rows(np.random.default_rng(0), 1),
                         deadline=time.monotonic() - 0.1)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=5)
        assert svc.queue_depth() == 0
        svc.stop()

    def test_expired_in_queue_refused_before_device_call(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, start=False)
        rng = np.random.default_rng(0)
        doomed = svc.submit(rows(rng, 1),
                            deadline=time.monotonic() + 0.05)
        alive = svc.submit(rows(rng, 1))
        time.sleep(0.1)  # the staged queue lets the deadline lapse
        svc.start()
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert np.asarray(alive.result(timeout=10)).shape == (1, 4)
        svc.stop()


class TestRetryAfterHint:
    def test_overloaded_carries_drain_estimate(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, queue_capacity=2,
                               start=False)
        rng = np.random.default_rng(0)
        # no dispatch observed yet: the hint is honestly None
        svc.submit(rows(rng, 1))
        svc.submit(rows(rng, 1))
        with pytest.raises(ServiceOverloaded) as ei:
            svc.submit(rows(rng, 1))
        assert ei.value.retry_after_ms is None
        svc.start()
        svc.predict(rows(rng, 1), timeout=60)  # establishes a rate
        svc.stop()
        # the drain-rate EWMA now yields a bounded positive hint
        hint = svc._batcher.retry_after_ms(depth=4)
        assert hint is not None and 1.0 <= hint <= 10_000.0

    def test_prediction_service_shim_retries_once(self, monkeypatch):
        from bigdl_tpu.optim.predictor import PredictionService
        shim = PredictionService(make_model(), batch_size=4)
        x = np.ones((1, 16), np.float32)
        expected = shim.predict(x)
        calls = []
        real_predict = shim.service.predict

        def flaky(arr, timeout=None):
            calls.append(1)
            if len(calls) == 1:
                raise ServiceOverloaded(4, 4, "m", retry_after_ms=1.0)
            return real_predict(arr, timeout=timeout)

        monkeypatch.setattr(shim.service, "predict", flaky)
        out = shim.predict(x)  # transient overload absorbed
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(expected))
        assert len(calls) == 2

        def always_full(arr, timeout=None):
            raise ServiceOverloaded(4, 4, "m", retry_after_ms=1.0)

        monkeypatch.setattr(shim.service, "predict", always_full)
        with pytest.raises(ServiceOverloaded):
            shim.predict(x)  # sustained overload is still felt upstream
        shim.service.stop()


# ===========================================================================
class TestReplicaSet:
    def _set(self, **kw):
        kw.setdefault("n_replicas", 2)
        kw.setdefault("input_spec", SPEC16)
        kw.setdefault("max_batch_size", 4)
        kw.setdefault("name", "rs")
        return ReplicaSet(make_model(), **kw)

    def test_least_queue_depth_routing(self):
        rs = self._set(start=False)  # staged: queues grow, none drain
        rng = np.random.default_rng(0)
        futs = [rs.submit(rows(rng, 1)) for _ in range(4)]
        # 4 staged single-row submits alternate 0,1,0,1 (shallowest
        # queue, ties to the lowest index)
        assert [s.queue_depth() for s in rs._replicas] == [2, 2]
        rs.start()
        for f in futs:
            assert np.asarray(f.result(timeout=30)).shape == (1, 4)
        rs.stop()

    def test_failover_on_injected_dispatch_error(self):
        reg = MetricRegistry()
        rs = self._set(
            fault_injector=FaultInjector("dispatch_error@target=0"),
            registry=reg, max_retries=2)
        rng = np.random.default_rng(0)
        x = rows(rng, 1)
        direct, _ = rs._replicas[1].model.apply(
            rs._replicas[1].params, rs._replicas[1].state, x,
            training=False)
        # replica 0 fails EVERY dispatch; the router must land every
        # request on replica 1 (first attempts that picked 0 fail over)
        outs = [np.asarray(rs.predict(x, timeout=30)) for _ in range(6)]
        for out in outs:
            np.testing.assert_array_equal(out, np.asarray(direct))
        snap = reg.snapshot()["counters"]
        assert snap["resilience/failovers"] >= 1
        # replica 0's failures eventually quarantine it
        assert rs.health_states()[0] in (DEGRADED, QUARANTINED)
        rs.stop()

    def test_all_quarantined_sheds_with_probation_hint(self):
        rs = self._set(health=HealthPolicy(probe_backoff_s=30.0))
        for h in rs._health:
            h.mark_dead()
        with pytest.raises(ServiceOverloaded) as ei:
            rs.submit(rows(np.random.default_rng(0), 1))
        # the retry-after hint is the next probation window
        assert ei.value.retry_after_ms is not None
        assert ei.value.retry_after_ms > 1000.0
        assert rs.stats()["resilience"]["resilience/sheds"] == 1
        rs.stop()

    def test_deadline_default_resolves_through_engine_chain(self):
        # serving_deadline_ms resolves as the other serving knobs do:
        # constructor argument > configure() > env > default
        configure(serving_deadline_ms=75.0)
        try:
            rs = self._set(start=False)
            assert rs.deadline_s == pytest.approx(0.075)
            rs.stop(drain=False)
            rs2 = self._set(start=False, deadline_ms=10.0)  # explicit wins
            assert rs2.deadline_s == pytest.approx(0.010)
            rs2.stop(drain=False)
        finally:
            reset_config()

    def test_supervisor_times_out_wedged_request(self):
        # staged replicas never dispatch — only the outside supervisor
        # can resolve the stuck request, via the propagated deadline
        rs = self._set(start=False, deadline_ms=50.0, max_retries=0)
        fut = rs.submit(np.ones((1, 16), np.float32))
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=10)
        snap = rs.stats()["resilience"]
        assert snap["resilience/deadline_timeouts"] >= 1
        # a parked batcher made NO progress since the deadline: that is
        # wedge evidence, so the replica's health must have recorded it
        assert rs._health[0].state != HEALTHY
        rs.stop(drain=False)


class TestReplicaSetReviewRegressions:
    """Post-review hardening gates (PR-10 code review)."""

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_routing_path_death_sweeps_stranded_running_requests(self):
        """Regression (found by the obs-plane PR's deadline-less load):
        a request mid-dispatch at replica death is marked RUNNING, so
        revive's backlog cancellation can't touch it — and if the
        ROUTING path revived the replica before the supervisor's next
        liveness poll, ``svc.alive`` read True again and the stranded
        request hung until its deadline (forever, with none).  The
        death handler now sweeps the dead replica's inflight entries
        itself.  Supervisor disabled here so only that sweep can
        rescue the victim."""
        rs = ReplicaSet(
            make_model(), n_replicas=2, input_spec=SPEC16,
            max_batch_size=4, batch_timeout_ms=0.0, deadline_ms=0,
            fault_injector=FaultInjector("replica_death@target=0,at=0",
                                         seed=0),
            name="stranded",
            health=HealthPolicy(probe_backoff_s=30.0))
        # no supervisor: the poll must not be what rescues the victim
        rs._ensure_supervisor_locked = lambda: None
        x = rows(np.random.default_rng(0), 1)
        victim = rs.submit(x)  # routed to r0, dies mid-dispatch
        deadline = time.monotonic() + 5.0
        while rs.replica(0).alive and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not rs.replica(0).alive, "death fault never fired"
        assert not victim.done()  # stranded: RUNNING on a dead batcher
        # the next routed request spots the dead batcher — the handler
        # must revive AND fail the victim over, not just revive
        other = rs.submit(x)
        np.testing.assert_allclose(np.asarray(other.result(10.0)),
                                   np.asarray(victim.result(10.0)))
        assert rs.stats()["resilience"]["resilience/failovers"] >= 1
        rs.stop()

    def test_both_quarantined_replicas_readmit(self):
        # regression: _pick used to consume EVERY due replica's one
        # probation-probe slot while dispatching only one, leaking
        # _probe_inflight on the rest — the leaked replicas refused
        # probes forever and could never re-admit
        rs = ReplicaSet(make_model(), n_replicas=2, input_spec=SPEC16,
                        max_batch_size=4, name="both-quar",
                        health=HealthPolicy(probe_backoff_s=0.05))
        for h in rs._health:
            h.mark_dead()
        x = rows(np.random.default_rng(0), 1)
        deadline = time.monotonic() + 20.0
        while rs.health_states() != [HEALTHY, HEALTHY]:
            assert time.monotonic() < deadline, (
                f"stuck at {rs.health_states()} — probe slot leaked")
            try:
                rs.predict(x, timeout=5.0)
            except ServiceOverloaded:
                time.sleep(0.02)  # before both probation windows open
        assert rs.stats()["resilience"]["resilience/readmissions"] == 2
        rs.stop()

    def test_congestion_deadline_is_not_a_health_failure(self):
        # regression: a batcher-refused queue expiry (pure congestion)
        # used to count against replica health, so a deadline storm
        # under overload could cascade-quarantine healthy replicas.
        # Only the supervisor's wedged-tagged timeout is evidence.
        rs = ReplicaSet(make_model(), n_replicas=1, input_spec=SPEC16,
                        max_batch_size=4, name="congest")
        from concurrent.futures import Future
        from bigdl_tpu.resilience.replica_set import _Route
        inner = Future()
        inner.set_exception(DeadlineExceeded("expired in queue"))
        r = _Route(None, Future(), None, 0)
        rs._inflight[1] = (r, 0, inner, False)
        rs._on_done(1)
        assert rs._health[0].state == HEALTHY  # congestion: no penalty
        wedged_exc = DeadlineExceeded("supervisor timeout")
        wedged_exc.wedged = True
        inner2 = Future()
        inner2.set_exception(wedged_exc)
        r2 = _Route(None, Future(), None, 0)
        rs._inflight[2] = (r2, 0, inner2, False)
        rs._on_done(2)
        assert rs._health[0].state == DEGRADED  # wedged: evidence
        assert rs.stats()["resilience"][
            "resilience/deadline_timeouts"] == 2
        rs.stop(drain=False)

    def test_exhausted_replicas_surface_real_error_not_shed(self):
        # regression: when every replica had been tried with retry
        # budget left, the request's REAL failure was replaced by a
        # fabricated ServiceOverloaded ("queue full") and counted as a
        # shed — a deterministic dispatch bug diagnosed as overload
        rs = ReplicaSet(make_model(), n_replicas=2, input_spec=SPEC16,
                        max_batch_size=4, name="exhaust",
                        max_retries=3,
                        fault_injector=FaultInjector("dispatch_error"))
        with pytest.raises(InjectedFault):  # the actual failure class
            rs.predict(rows(np.random.default_rng(0), 1), timeout=30)
        assert rs.stats()["resilience"]["resilience/sheds"] == 0
        rs.stop()

    def test_caller_bug_on_probe_does_not_extend_quarantine(self):
        # regression: a malformed request that happened to be a
        # quarantined replica's probation probe was recorded as a probe
        # FAILURE, doubling its backoff — the replica never saw it
        rs = ReplicaSet(make_model(), n_replicas=1, input_spec=SPEC16,
                        max_batch_size=4, name="callerbug",
                        health=HealthPolicy(probe_backoff_s=0.01))
        rs._health[0].mark_dead()
        time.sleep(0.05)  # probation window opens
        too_big = rows(np.random.default_rng(0), 9)  # > max_batch_size
        with pytest.raises(ValueError):
            rs.submit(too_big)
        # the probe slot was released without an outcome: the replica
        # is immediately probe-able again and a well-formed request
        # re-admits it
        out = rs.predict(rows(np.random.default_rng(1), 1), timeout=30)
        assert np.asarray(out).shape == (1, 4)
        assert rs.health_states() == [HEALTHY]
        rs.stop()

    def test_fault_plan_change_between_runs_is_honored(self):
        # regression: the FaultInjector was cached on the optimizer
        # forever, so clearing (or changing) Config.fault_plan between
        # optimize() calls on the same object was silently ignored
        configure(fault_plan="dispatch_delay@ms=0.1,count=1")
        try:
            losses, opt, _ = tiny_run(iters=4)
            assert opt._fault_injector is not None
            configure(fault_plan="")
            opt.set_end_when(optim.max_iteration(8)).optimize()
            assert opt._fault_injector is None  # honored: back to inert
        finally:
            reset_config()

    def test_predict_wait_timeout_normalized_to_deadline_exceeded(self):
        # regression: on py<3.11 the result-wait expiry raised
        # concurrent.futures.TimeoutError (NOT builtin TimeoutError),
        # slipping past callers' deadline handling
        rs = ReplicaSet(make_model(), n_replicas=1, input_spec=SPEC16,
                        max_batch_size=4, name="wait", start=False)
        with pytest.raises(DeadlineExceeded):
            rs.predict(rows(np.random.default_rng(0), 1), timeout=0.1)
        rs.stop(drain=False)


class TestReplicaDeathSubprocess:
    """The ISSUE-10 acceptance gate, in a REAL subprocess: kill one
    replica's batcher mid-traffic; zero lost, zero wrong, quarantine
    and readmission all present in the metrics."""

    def test_kill_quarantine_failover_readmit(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = (REPO + os.pathsep + env.get("PYTHONPATH", "")
                             ).rstrip(os.pathsep)
        proc = subprocess.run(
            [sys.executable, CHILD], env=env, capture_output=True,
            text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        counts = report["counts"]
        assert report["lost"] == 0
        assert counts["wrong"] == 0
        assert counts["ok"] > 100          # real traffic flowed
        assert report["saw_quarantine"]    # the death was visible
        res = report["resilience"]
        assert res["resilience/replica_deaths"] == 1
        assert res["resilience/quarantines"] == 1
        assert res["resilience/revivals"] == 1
        assert res["resilience/readmissions"] == 1  # probation worked
        assert res["resilience/failovers"] >= 1     # stranded work moved
        # the killed replica is back in rotation by the end
        assert report["final_health"] == ["healthy"] * 4


# ===========================================================================
class RecordingSummary:
    def __init__(self):
        self.losses = []

    def add_train_step(self, step, loss, lr, throughput):
        self.losses.append(loss)

    def add_scalar(self, *a):
        pass

    def trigger_for(self, name):
        return None


def tiny_run(iters=6, k=1, guard=None, plan=None, ckpt=None, seed=7):
    if plan is not None:
        configure(fault_plan=plan)
    try:
        rng = np.random.default_rng(0)
        samples = [Sample(rng.normal(0, 1, (16,)).astype(np.float32),
                          np.int32(rng.integers(0, 4)))
                   for _ in range(64)]
        model = nn.Sequential(nn.Linear(16, 16), nn.ReLU(),
                              nn.Linear(16, 4), nn.LogSoftMax())
        rec = RecordingSummary()
        opt = (optim.LocalOptimizer(model,
                                    DataSet.array(samples)
                                    >> SampleToMiniBatch(16),
                                    nn.ClassNLLCriterion())
               .set_optim_method(optim.SGD(learning_rate=0.1))
               .set_seed(seed)
               .set_train_summary(rec)
               .set_steps_per_dispatch(k)
               .set_end_when(optim.max_iteration(iters)))
        if guard is not None:
            opt.set_numeric_guard(guard)
        if ckpt is not None:
            opt.set_checkpoint(ckpt, optim.several_iteration(1))
        opt.optimize()
        return np.asarray(rec.losses), opt, model
    finally:
        if plan is not None:
            reset_config()


class TestNumericGuard:
    def test_skip_gates_update_and_continues(self):
        losses, opt, model = tiny_run(guard="skip",
                                      plan="nonfinite_grads@at=2")
        assert len(losses) == 6
        assert not np.isfinite(losses[2])       # the poison was real
        assert np.isfinite(losses[3:]).all()    # training recovered
        snap = opt.metrics.registry.snapshot()["counters"]
        assert snap["resilience/steps_skipped"] == 1
        assert snap["resilience/nonfinite_steps"] == 1
        for leaf in jax_leaves(model._params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_skip_leaves_state_as_if_step_never_ran(self):
        # a poisoned FIRST step under skip must land exactly where a
        # run that never saw the poison landed after its first step:
        # losses from step 1 on are bitwise-identical because params
        # after the skipped step are bitwise the init params
        clean, _, _ = tiny_run(iters=5)
        poisoned, _, _ = tiny_run(iters=6, guard="skip",
                                  plan="corrupt_batch@at=0")
        # step j of the clean run sees the SAME params as step j+1 of
        # the poisoned run but a different batch, so compare the states
        # we can pin bitwise: the skipped step's loss is non-finite and
        # every later loss is finite
        assert not np.isfinite(poisoned[0])
        assert np.isfinite(poisoned[1:]).all()

    def test_abort_raises_at_exact_iteration(self):
        with pytest.raises(NonFiniteStepError) as ei:
            tiny_run(guard="abort", plan="corrupt_batch@at=3")
        assert ei.value.step == 3
        assert ei.value.policy == "abort"

    def test_abort_at_exact_iteration_fused_k4(self):
        # the poisoned step sits mid-block: the replay must still name
        # iteration 5, not the block boundary
        with pytest.raises(NonFiniteStepError) as ei:
            tiny_run(k=4, guard="abort", plan="nonfinite_grads@at=5",
                     iters=8)
        assert ei.value.step == 5

    def test_rollback_restores_latest_valid_and_completes(self):
        with tempfile.TemporaryDirectory() as d:
            losses, opt, _ = tiny_run(
                guard="rollback", plan="nonfinite_grads@at=4,count=1",
                ckpt=d)
        assert len(losses) == 6
        assert np.isfinite(losses).all()   # the re-run step was clean
        snap = opt.metrics.registry.snapshot()["counters"]
        assert snap["resilience/rollbacks"] == 1
        assert snap["resilience/nonfinite_steps"] == 1

    def test_rollback_without_checkpoint_refused_loudly(self):
        with pytest.raises(ValueError, match="rollback"):
            tiny_run(guard="rollback")

    def test_bad_policy_refused_loudly(self):
        model = nn.Sequential(nn.Linear(4, 2))
        opt = optim.LocalOptimizer(
            model, DataSet.array(
                [Sample(np.zeros(4, np.float32), np.int32(0))])
            >> SampleToMiniBatch(1), nn.ClassNLLCriterion())
        with pytest.raises(ValueError, match="numeric_guard"):
            opt.set_numeric_guard("explode")

    def test_env_policy_resolution_and_explicit_none_override(self):
        configure(numeric_guard="skip")
        try:
            model = nn.Sequential(nn.Linear(4, 2))
            opt = optim.LocalOptimizer(
                model, DataSet.array(
                    [Sample(np.zeros(4, np.float32), np.int32(0))])
                >> SampleToMiniBatch(1), nn.ClassNLLCriterion())
            assert opt._resolved_numeric_guard() == "skip"
            # explicit None IS the inert policy, not "unset"
            opt.set_numeric_guard(None)
            assert opt._resolved_numeric_guard() == "off"
        finally:
            reset_config()


def jax_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def distri_run(iters=6, k=1, guard=None, plan=None):
    if plan is not None:
        configure(fault_plan=plan)
    try:
        rng = np.random.default_rng(0)
        samples = [Sample(rng.normal(0, 1, (16,)).astype(np.float32),
                          np.int32(rng.integers(0, 4)))
                   for _ in range(128)]
        model = nn.Sequential(nn.Linear(16, 16), nn.ReLU(),
                              nn.Linear(16, 4), nn.LogSoftMax())
        rec = RecordingSummary()
        opt = (optim.DistriOptimizer(model,
                                     DataSet.array(samples)
                                     >> SampleToMiniBatch(64),
                                     nn.ClassNLLCriterion())
               .set_optim_method(optim.SGD(learning_rate=0.1))
               .set_seed(7)
               .set_train_summary(rec)
               .set_steps_per_dispatch(k)
               .set_end_when(optim.max_iteration(iters)))
        if guard is not None:
            opt.set_numeric_guard(guard)
        opt.optimize()
        return np.asarray(rec.losses), opt
    finally:
        if plan is not None:
            reset_config()


class TestNumericGuardDistri:
    """The SPMD half of the guard: the finite verdict is a mesh-global
    ``pmin`` so every chip gates its owned ZeRO-1 slice identically."""

    def test_skip_all_finite_bitwise_inert_on_mesh(self):
        base, _ = distri_run()
        skip, _ = distri_run(guard="skip")
        np.testing.assert_array_equal(base, skip)

    def test_skip_poisoned_step_fused_k4(self):
        losses, opt = distri_run(k=4, guard="skip", iters=8,
                                 plan="nonfinite_grads@at=3")
        assert not np.isfinite(losses[3])
        assert np.isfinite(losses[4:]).all()
        snap = opt.metrics.registry.snapshot()["counters"]
        assert snap["resilience/steps_skipped"] == 1


# ===========================================================================
class TestInertness:
    """The ISSUE-10 acceptance gate: with ``fault_plan=None`` no
    injector exists and the numeric guard over all-finite training
    changes NOTHING — bitwise loss sequences, equal dispatch counts,
    bitwise final params, serving bitwise-equal to direct apply."""

    @pytest.mark.parametrize("k", [1, 4])
    def test_numeric_guard_all_finite_bitwise_inert(self, k):
        base_l, base_o, base_m = tiny_run(iters=8, k=k)
        skip_l, skip_o, skip_m = tiny_run(iters=8, k=k, guard="skip")
        np.testing.assert_array_equal(base_l, skip_l)
        assert base_o._dispatch_count == skip_o._dispatch_count
        for a, b in zip(jax_leaves(base_m._params),
                        jax_leaves(skip_m._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("k", [1, 4])
    def test_fault_plan_none_bitwise_inert(self, k):
        # fault_plan="" builds NO injector (structural inertness) and
        # two identical runs under that state are bitwise-equal — the
        # driver's fault sites are provably never entered
        assert FaultInjector.from_config() is None
        a_l, a_o, _ = tiny_run(iters=8, k=k)
        b_l, b_o, _ = tiny_run(iters=8, k=k)
        assert a_o._fault_injector is None
        np.testing.assert_array_equal(a_l, b_l)
        assert a_o._dispatch_count == b_o._dispatch_count

    def test_replica_set_serving_bitwise_equals_bare_engine(self):
        # the resilience front adds NOTHING to the serving numerics:
        # every ReplicaSet result is bitwise-equal to the bare
        # InferenceService of PR 5 (which tests/test_serving.py in turn
        # pins bitwise to direct ``model.apply`` per coalesced bucket)
        model = make_model()
        bare = InferenceService(model, input_spec=SPEC16,
                                max_batch_size=4, name="bare")
        rs = ReplicaSet(model, n_replicas=2, input_spec=SPEC16,
                        max_batch_size=4, name="inert")
        assert rs._faults is None  # no plan, no injector object
        rng = np.random.default_rng(5)
        for n in (1, 2, 4):
            x = rows(rng, n)
            out = np.asarray(rs.predict(x, timeout=60))
            ref = np.asarray(bare.predict(x, timeout=60))
            np.testing.assert_array_equal(out, ref)
        assert rs.stats()["resilience"]["resilience/sheds"] == 0
        bare.stop()
        rs.stop()


# ===========================================================================
class TestReplicaElasticity:
    """ISSUE 14 satellite: ``ReplicaSet.set_replica_count`` grow/shrink
    — unit-tested independently of the autoscaler that drives it."""

    def test_grow_warms_off_the_routing_path(self):
        rs = ReplicaSet(make_model(), n_replicas=1, input_spec=SPEC16,
                        max_batch_size=4, buckets="top", name="grow",
                        start=False)
        rep = rs.set_replica_count(3)
        assert rep == {"active": 3, "added": [1, 2], "retired": []}
        for ix in (1, 2):
            svc = rs.replica(ix)
            # fully AOT-warmed BEFORE admission: the grown replica
            # never serves a compile stall
            assert svc.warmed_up
            # same trace bill replica 0 paid at construction (warmup
            # probes + bucket executables)
            assert svc.compile_count == rs.replica(0).compile_count
        # staged routing spreads across all three (least-queue-depth)
        rng = np.random.default_rng(0)
        futs = [rs.submit(rows(rng, 1), timeout=30) for _ in range(3)]
        assert [rs.replica(i).queue_depth() for i in range(3)] \
            == [1, 1, 1]
        rs.start()
        for f in futs:
            f.result(timeout=30)
        rs.stop()

    def test_shrink_drains_queued_work_without_a_death(self):
        rs = ReplicaSet(make_model(), n_replicas=2, input_spec=SPEC16,
                        max_batch_size=4, buckets="top",
                        name="shrink", start=False)
        rng = np.random.default_rng(1)
        # stage work onto BOTH replicas, then retire one: its queued
        # futures must resolve (inline drain), not cancel or fail over
        futs = [rs.submit(rows(rng, 1), timeout=60) for _ in range(4)]
        assert rs.replica(1).queue_depth() == 2
        rep = rs.set_replica_count(1, timeout=30)
        assert rep["retired"] == [1]
        done = [f for f in futs if f.done()]
        assert len(done) == 2  # exactly r1's staged work drained
        for f in done:
            assert f.exception() is None
        snap = rs.registry.snapshot()["counters"]
        assert snap["resilience/replica_deaths"] == 0
        assert snap["resilience/replicas_retired"] == 1
        # retired slot: excluded from routing, executables released
        assert rs.n_replicas == 1 and rs.active_indices() == [0]
        assert rs.replica(1).params is None
        f5 = rs.submit(rows(rng, 1), timeout=30)
        assert rs.replica(0).queue_depth() == 3
        rs.start()
        for f in futs + [f5]:
            f.result(timeout=30)
        rs.stop()

    def test_shrink_under_live_load_resolves_everything(self):
        rs = ReplicaSet(make_model(), n_replicas=3, input_spec=SPEC16,
                        max_batch_size=4, buckets="top",
                        name="live-shrink")
        rng = np.random.default_rng(2)
        errs = []
        stop = threading.Event()

        def caller():
            while not stop.is_set():
                try:
                    rs.predict(rows(rng, 1), timeout=30)
                except Exception as e:
                    errs.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=caller) for _ in range(4)]
        for t in threads:
            t.start()
        rs.set_replica_count(1, timeout=30)
        rs.set_replica_count(2, timeout=30)
        stop.set()
        for t in threads:
            t.join()
        assert errs == []
        snap = rs.registry.snapshot()["counters"]
        assert snap["resilience/replica_deaths"] == 0
        rs.stop()

    def test_slot_reuse_and_health_reset(self):
        rs = ReplicaSet(make_model(), n_replicas=2, input_spec=SPEC16,
                        max_batch_size=4, buckets="top", name="reuse",
                        start=False)
        rs.set_replica_count(1)
        assert rs.health_snapshot()["retired_slots"] == [1]
        rep = rs.set_replica_count(2)
        assert rep["added"] == [1]  # the retired slot, reused
        assert rs.health_snapshot()["retired_slots"] == []
        assert rs.replica(1).warmed_up
        assert rs.health_states()[1] == HEALTHY  # fresh ledger
        assert rs.total_slots == 2
        rs.stop()

    def test_bounds_and_lifecycle_errors(self):
        rs = ReplicaSet(make_model(), n_replicas=1, input_spec=SPEC16,
                        max_batch_size=4, buckets="top",
                        name="bounds", start=False)
        with pytest.raises(ValueError):
            rs.set_replica_count(0)
        assert rs.set_replica_count(1) == {"active": 1, "added": [],
                                           "retired": []}
        rs.stop()
        from bigdl_tpu.serving import ServiceClosed
        with pytest.raises(ServiceClosed):
            rs.set_replica_count(2)

    def test_stats_and_health_exclude_retired(self):
        rs = ReplicaSet(make_model(), n_replicas=2, input_spec=SPEC16,
                        max_batch_size=4, buckets="top",
                        name="statsx", start=False)
        rs.set_replica_count(1)
        health = rs.health_snapshot()
        assert health["ok"] is True  # a retired slot is not an incident
        assert [r["ix"] for r in health["replicas"]] == [0]
        stats = rs.stats()
        assert [r["ix"] for r in stats["replicas"]] == [0]
        assert stats["retired_slots"] == [1]
        rs.stop()


# ===========================================================================
class TestStagerProducerFailure:
    """Satellite: an exception in the background batch-assembly thread
    must surface as the ORIGINAL error on the next ``take()`` instead
    of risking an indefinite block."""

    def _stager_over(self, source_iter, batch=4):
        import jax.numpy as jnp
        mt = MTSampleToMiniBatch(batch, workers=2)
        return DeviceBlockStager(
            mt(iter(source_iter)),
            lambda xs, ys: (jax_tree_map(jnp.asarray, xs),
                            None if ys is None
                            else jax_tree_map(jnp.asarray, ys)))

    def test_raising_source_surfaces_original_error(self):
        class Boom(RuntimeError):
            pass

        def source():
            rng = np.random.default_rng(0)
            for i in range(6):
                yield Sample(rng.normal(0, 1, (8,)).astype(np.float32),
                             np.int32(0))
            raise Boom("decoder exploded")

        stager = self._stager_over(source())
        xs, ys, sizes = stager.take(1, 10**9)  # first block is fine
        assert sizes == [4]
        t0 = time.monotonic()
        with pytest.raises(Boom, match="decoder exploded"):
            while True:  # the NEXT pull must raise, never wedge
                stager.take(1, 10**9)
        assert time.monotonic() - t0 < 30.0

    def test_dead_producer_without_delivery_surfaces(self, monkeypatch):
        # pathological case: the producer thread never runs at all (a
        # Thread.start that silently no-ops stands in for a thread the
        # OS killed before its first byte) — the consumer must raise,
        # not block forever on its queue
        from bigdl_tpu.dataset import prefetch as prefetch_mod

        class DeadThread:
            def __init__(self, *a, **kw):
                pass

            def start(self):
                pass

            def is_alive(self):
                return False

            def join(self, timeout=None):
                pass

        monkeypatch.setattr(prefetch_mod.threading, "Thread", DeadThread)
        mt = MTSampleToMiniBatch(2, workers=1)
        it = mt(iter([Sample(np.zeros(4, np.float32), np.int32(0))]))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="producer thread died"):
            next(it)
        assert time.monotonic() - t0 < 30.0


def jax_tree_map(f, tree):
    import jax
    return jax.tree_util.tree_map(f, tree)


# ===========================================================================
class TestAsyncSnapshotWriterErrorContext:
    """Satellite: deferred-error reports name the snapshot path and
    step, so rollback policy can log exactly what it fell back from."""

    def test_deferred_error_names_path_and_step(self):
        from bigdl_tpu.checkpoint.snapshot import AsyncSnapshotWriter
        w = AsyncSnapshotWriter()

        def bad():
            raise IOError("disk full")

        w.submit(bad, context="step 42 → /ckpt/model.42")
        with pytest.raises(RuntimeError) as ei:
            w.drain()
        assert "step 42" in str(ei.value)
        assert "/ckpt/model.42" in str(ei.value)
        assert isinstance(ei.value.__cause__, IOError)
        w.close(raise_errors=False)

    def test_manager_save_threads_context_through(self, monkeypatch,
                                                  tmp_path):
        from bigdl_tpu.checkpoint import manager as manager_mod
        from bigdl_tpu.checkpoint.manager import CheckpointManager

        def failing_write(path, **kw):
            raise IOError(f"cannot write {path}")

        monkeypatch.setattr(manager_mod, "write_snapshot",
                            failing_write)
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        params = {"w": np.zeros((2, 2), np.float32)}
        mgr.save(3, params)
        with pytest.raises(RuntimeError) as ei:
            mgr.wait()  # drain surfaces the deferred error
        msg = str(ei.value)
        assert "step 3" in msg and str(tmp_path) in msg
        mgr.close(raise_errors=False)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
