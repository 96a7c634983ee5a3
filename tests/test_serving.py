"""bigdl_tpu.serving — dynamic-batching inference engine tests.

The load-bearing gates (ISSUE 5 acceptance):

- **Coalescing proof**: 16 threads × 4 single-row submits resolve in
  ``ceil(requests / max_batch_size)`` device dispatches (≪ request
  count), with ZERO new compiles after warmup (trace-counter assertion
  — the serving analog of graftlint GL106).
- **Bitwise correctness**: every coalesced, bucket-padded result equals
  a direct per-request ``model.apply`` forward bit for bit (zero-pad
  rows provably don't leak into real rows).
- **Backpressure**: a full bounded queue raises ``ServiceOverloaded``
  with the depth in the message; shutdown drains cleanly.

All concurrency tests are event-driven (barriers, futures, the
``start=False`` staging hook) — no sleep-based synchronization.
"""

import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.module import Module
from bigdl_tpu.optim.predictor import PredictionService, Predictor
from bigdl_tpu.serving import (
    InferenceService, LatencyReservoir, ModelRegistry, ServiceClosed,
    ServiceOverloaded, row_buckets,
)


def make_model(din=16, dout=4):
    return nn.Sequential(nn.Linear(din, 32), nn.ReLU(),
                         nn.Linear(32, dout), nn.SoftMax()).initialize(0)


def rows(rng, n, din=16):
    return rng.normal(0, 1, (n, din)).astype(np.float32)


SPEC16 = ((16,), np.float32)


class TestBuckets:
    def test_power_of_two_ladder(self):
        assert row_buckets(8) == (1, 2, 4, 8)
        assert row_buckets(1) == (1,)

    def test_non_pow2_max_is_top_bucket(self):
        assert row_buckets(12) == (1, 2, 4, 8, 12)

    def test_warmup_compiles_each_bucket_once(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=8, start=False)
        assert svc.warmed_up
        # one trace per bucket executable, nothing else
        assert svc.compile_count == len(svc.buckets)
        assert svc.output_row_shape() == (4,)
        # warmup is idempotent — no second compile sweep
        assert svc.warmup(SPEC16) == {}
        assert svc.compile_count == len(svc.buckets)
        svc.stop()


class TestServingKnobs:
    """``parse_row_buckets``: the ``Config.serving_row_buckets`` spec
    grammar."""

    def test_spec_grammar(self):
        from bigdl_tpu.serving.service import parse_row_buckets
        assert parse_row_buckets("", 32) == (1, 2, 4, 8, 16, 32)
        assert parse_row_buckets("pow2", 32) == (1, 2, 4, 8, 16, 32)
        assert parse_row_buckets("top", 32) == (32,)
        assert parse_row_buckets("8,16,32", 32) == (8, 16, 32)

    @pytest.mark.parametrize("spec", ["8,x", "16,8", "8,8,16", "0,8",
                                      "4,8"])
    def test_bad_specs_rejected(self, spec):
        from bigdl_tpu.serving.service import parse_row_buckets
        with pytest.raises(ValueError):
            parse_row_buckets(spec, 32)

    def test_explicit_tuple_validated_through_same_grammar(self):
        from bigdl_tpu.serving.service import parse_row_buckets
        with pytest.raises(ValueError):
            parse_row_buckets("16,8", 8)


class TestCoalescing:
    """The acceptance gate: 16-thread single-row load."""

    N_THREADS, PER_THREAD, MAX_BATCH = 16, 4, 8

    def _staged_load(self):
        model = make_model()
        svc = InferenceService(model, input_spec=SPEC16,
                               max_batch_size=self.MAX_BATCH,
                               queue_capacity=256, start=False)
        warm_compiles = svc.compile_count
        rng = np.random.default_rng(7)
        xs = [rows(rng, 1) for _ in range(self.N_THREADS * self.PER_THREAD)]
        futs = [None] * len(xs)
        barrier = threading.Barrier(self.N_THREADS)

        def worker(t):
            barrier.wait()
            for i in range(self.PER_THREAD):
                k = t * self.PER_THREAD + i
                futs[k] = svc.submit(xs[k])

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(self.N_THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        # everything queued BEFORE the first dispatch — deterministic
        assert svc.queue_depth() == len(xs)
        svc.start()
        outs = [f.result(timeout=60) for f in futs]
        return model, svc, xs, outs, warm_compiles

    def test_dispatch_budget_and_bitwise_outputs(self):
        model, svc, xs, outs, warm = self._staged_load()
        n_req = len(xs)
        stats = svc.stats()
        budget = math.ceil(n_req / self.MAX_BATCH) + len(svc.buckets)
        assert stats["dispatch_count"] <= budget, stats
        assert stats["dispatch_count"] < n_req  # coalescing, not 1:1
        # bitwise equality against per-request direct forwards
        for x, out in zip(xs, outs):
            direct, _ = model.apply(svc.params, svc.state, x,
                                    training=False)
            np.testing.assert_array_equal(out, np.asarray(direct))
        # zero new compiles after warmup (GL106-for-serving)
        assert svc.compile_count == warm
        assert stats["compile_count"] == warm
        # fully staged queue → perfectly occupied buckets
        assert stats["mean_batch_occupancy"] == 1.0
        assert stats["requests_completed"] == n_req
        svc.stop()

    def test_live_threads_blocking_predict(self):
        """predict() (blocking sugar) from concurrent threads: pure
        correctness under live interleaving, no dispatch-count claim."""
        model = make_model()
        svc = InferenceService(model, input_spec=SPEC16, max_batch_size=8,
                               batch_timeout_ms=1.0)
        rng = np.random.default_rng(3)
        xs = [rows(rng, n) for n in (1, 3, 5, 8, 2, 1, 7, 4)]
        errs = []

        def worker(x):
            try:
                out = svc.predict(x, timeout=60)
                direct, _ = model.apply(svc.params, svc.state, x,
                                        training=False)
                np.testing.assert_array_equal(out, np.asarray(direct))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(x,)) for x in xs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert svc.stats()["requests_completed"] == sum(
            x.shape[0] for x in xs)
        svc.stop()

    def test_mixed_sizes_pad_to_bucket_bitwise(self):
        """Odd-sized coalesced groups pad with zeros to the bucket; the
        pad provably does not leak into real rows (bitwise equality
        between bucket sizes IS the invariant check)."""
        model = make_model()
        svc = InferenceService(model, input_spec=SPEC16, max_batch_size=8,
                               start=False)
        rng = np.random.default_rng(11)
        xs = [rows(rng, n) for n in (3, 2)]  # coalesce to 5 → bucket 8
        futs = [svc.submit(x) for x in xs]
        svc.start()
        outs = [f.result(timeout=60) for f in futs]
        assert svc.stats()["dispatch_count"] == 1
        for x, out in zip(xs, outs):
            direct, _ = model.apply(svc.params, svc.state, x,
                                    training=False)
            np.testing.assert_array_equal(out, np.asarray(direct))
        svc.stop()


class TestBackpressure:
    def test_overloaded_then_drain(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, queue_capacity=4,
                               start=False)
        x = rows(np.random.default_rng(0), 1)
        futs = [svc.submit(x) for _ in range(4)]
        with pytest.raises(ServiceOverloaded) as ei:
            svc.submit(x)
        assert ei.value.queue_depth == 4 and ei.value.capacity == 4
        assert "depth=4" in str(ei.value)
        assert svc.stats()["requests_rejected"] == 1
        # backpressure clears once the batcher runs
        svc.start()
        for f in futs:
            assert f.result(timeout=60).shape == (1, 4)
        svc.stop()
        assert svc.stats()["queue_depth"] == 0

    def test_stop_drains_accepted_work(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, start=False)
        x = rows(np.random.default_rng(1), 2)
        futs = [svc.submit(x) for _ in range(5)]
        svc.stop(drain=True)  # never-started batcher drains inline
        for f in futs:
            assert f.result(timeout=0).shape == (2, 4)
        with pytest.raises(ServiceClosed):
            svc.submit(x)

    def test_stop_no_drain_cancels_backlog(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, start=False)
        x = rows(np.random.default_rng(2), 1)
        futs = [svc.submit(x) for _ in range(3)]
        svc.stop(drain=False)
        assert all(f.cancelled() for f in futs)
        assert svc.stats()["requests_cancelled"] == 3

    def test_stop_no_drain_cancels_on_running_batcher(self):
        """Regression: with the batcher RUNNING, drain=False must cancel
        the backlog, not quietly dispatch it.  The first dispatch is
        gated on an Event so the backlog deterministically builds while
        the batcher thread is busy."""
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=1, start=False)
        gate = threading.Event()
        entered = threading.Event()
        inner = svc._batcher._dispatch_fn

        def gated(reqs):
            entered.set()
            gate.wait(timeout=60)
            inner(reqs)

        svc._batcher._dispatch_fn = gated
        x = rows(np.random.default_rng(12), 1)
        first = svc.submit(x)
        svc.start()
        assert entered.wait(timeout=60)  # batcher busy inside dispatch 1
        backlog = [svc.submit(x) for _ in range(3)]
        stopper = threading.Thread(target=svc.stop,
                                   kwargs={"drain": False})
        stopper.start()
        gate.set()
        stopper.join(timeout=60)
        assert not stopper.is_alive()
        assert first.result(timeout=60).shape == (1, 4)  # in-flight wins
        assert all(f.cancelled() for f in backlog)
        assert svc.stats()["requests_cancelled"] == 3

    def test_running_service_stop_resolves_everything(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=8, batch_timeout_ms=1.0)
        x = rows(np.random.default_rng(3), 1)
        futs = [svc.submit(x) for _ in range(20)]
        svc.stop(drain=True)
        assert all(f.done() and not f.cancelled() for f in futs)
        stats = svc.stats()
        assert stats["requests_completed"] == 20
        assert stats["queue_depth"] == 0


class TestServiceSurface:
    def test_oversized_submit_rejected_predict_chunks(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4)
        x = rows(np.random.default_rng(5), 11)
        with pytest.raises(ValueError, match="max_batch_size"):
            svc.submit(x)
        out = svc.predict(x, timeout=60)
        direct, _ = svc.model.apply(svc.params, svc.state, x,
                                    training=False)
        np.testing.assert_array_equal(out, np.asarray(direct))
        svc.stop()

    def test_huge_predict_through_tiny_queue(self):
        """Regression: predict() must window its chunk submissions so a
        large input can't self-overflow the bounded queue (the old
        submit-everything loop raised ServiceOverloaded at ~capacity
        chunks)."""
        model = make_model()
        svc = InferenceService(model, input_spec=SPEC16, max_batch_size=2,
                               queue_capacity=4, batch_timeout_ms=0.0)
        x = rows(np.random.default_rng(15), 64)  # 32 chunks >> capacity
        out = svc.predict(x, timeout=120)
        direct, _ = model.apply(svc.params, svc.state, x, training=False)
        np.testing.assert_array_equal(out, np.asarray(direct))
        svc.stop()

    def test_predict_timeout_is_a_shared_deadline(self):
        """Regression: timeout bounds the whole predict(), not each
        chunk future — a parked batcher must time the call out in ~one
        timeout, not chunks x timeout."""
        import concurrent.futures
        import time as _time
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=2, queue_capacity=64,
                               start=False)
        x = rows(np.random.default_rng(16), 32)  # 16 chunks
        t0 = _time.monotonic()
        with pytest.raises((TimeoutError, concurrent.futures.TimeoutError)):
            svc.predict(x, timeout=0.3)
        assert _time.monotonic() - t0 < 3.0  # not 16 x 0.3 compounding
        svc.stop(drain=False)

    def test_empty_input_shape(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=4, start=False)
        out = svc.predict(np.empty((0, 16), np.float32))
        assert out.shape == (0, 4) and out.dtype == np.float32
        svc.stop()

    def test_deferred_spec_warms_on_first_request(self):
        svc = InferenceService(make_model(), max_batch_size=4)
        assert not svc.warmed_up
        out = svc.predict(rows(np.random.default_rng(6), 2), timeout=60)
        assert out.shape == (2, 4)
        assert svc.warmed_up
        assert svc.compile_count == len(svc.buckets)
        svc.stop()

    def test_deferred_warmup_concurrent_first_requests(self):
        """Regression: concurrent FIRST requests must all block until
        every bucket is compiled — a submitter must never observe a
        partially-populated executable dict (KeyError on dispatch)."""
        svc = InferenceService(make_model(), max_batch_size=8,
                               batch_timeout_ms=1.0)
        rng = np.random.default_rng(13)
        sizes = [1, 5, 3, 8, 2, 7, 4, 6]
        xs = [rows(rng, n) for n in sizes]
        barrier = threading.Barrier(len(sizes))
        errs = []

        def worker(x):
            barrier.wait()
            try:
                out = svc.predict(x, timeout=60)
                assert out.shape == (x.shape[0], 4)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(x,))
                   for x in xs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        assert svc.compile_count == len(svc.buckets)
        svc.stop()

    def test_pytree_input_model(self):
        class TwoTower(Module):
            def init(self, rng):
                k1, k2 = jax.random.split(rng)
                return {"a": jax.random.normal(k1, (6, 3)),
                        "b": jax.random.normal(k2, (5, 3))}, {}

            def apply(self, params, state, input, *, training=False,
                      rng=None):
                a, b = input
                return a @ params["a"] + b @ params["b"], state

        model = TwoTower().initialize(0)
        svc = InferenceService(
            model, input_spec=(((6,), np.float32), ((5,), np.float32)),
            max_batch_size=4, start=False)
        rng = np.random.default_rng(8)
        x = (rng.normal(0, 1, (3, 6)).astype(np.float32),
             rng.normal(0, 1, (3, 5)).astype(np.float32))
        fut = svc.submit(x)
        svc.start()
        out = fut.result(timeout=60)
        direct, _ = model.apply(svc.params, svc.state, x, training=False)
        np.testing.assert_array_equal(out, np.asarray(direct))
        svc.stop()

    def test_malformed_request_fails_alone(self):
        """A bad request must be rejected at submit — not poison the
        coalesced group it would have joined."""
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=8, start=False)
        good = svc.submit(rows(np.random.default_rng(19), 2))
        with pytest.raises(ValueError, match="input_spec"):
            svc.submit(np.ones((1, 8), np.float32))  # wrong trailing dim
        svc.start()
        assert good.result(timeout=60).shape == (2, 4)  # unharmed
        svc.stop()

    def test_dtype_mismatch_coerced_like_jnp_asarray(self):
        """float64 (the numpy default) serves as f32 — the historical
        jnp.asarray behavior — instead of poisoning the group through
        np.concatenate's silent promotion."""
        model = make_model()
        svc = InferenceService(model, input_spec=SPEC16, max_batch_size=4)
        x32 = rows(np.random.default_rng(20), 2)
        out64 = svc.predict(x32.astype(np.float64), timeout=60)
        out32 = svc.predict(x32, timeout=60)
        assert out64.dtype == np.float32
        np.testing.assert_array_equal(out64, out32)
        svc.stop()

    def test_non_row_tracking_model_refused_at_deploy(self):
        """A model whose output rows come from static metadata cannot
        be served by per-request slicing — warmup must refuse it."""

        class StaticRows(Module):
            def init(self, rng):
                return {"w": jax.random.normal(rng, (3, 3))}, {}

            def apply(self, params, state, input, *, training=False,
                      rng=None):
                # output rows fixed at 4 regardless of input rows
                pooled = jnp.sum(input, axis=0, keepdims=True)
                return jnp.tile(pooled @ params["w"], (4, 1)), state

        with pytest.raises(ValueError, match="not servable"):
            InferenceService(StaticRows().initialize(0),
                             input_spec=((3,), np.float32),
                             max_batch_size=4, start=False)

    def test_stats_schema(self):
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=8)
        svc.predict(rows(np.random.default_rng(9), 3), timeout=60)
        s = svc.stats()
        for key in ("requests_submitted", "requests_completed",
                    "dispatch_count", "mean_batch_occupancy",
                    "throughput_rps", "queue_depth", "latency_ms",
                    "latency_ms_by_bucket", "compile_count", "buckets",
                    "model"):
            assert key in s, key
        assert s["latency_ms"] is not None
        assert {"p50", "p95", "p99", "mean"} <= set(s["latency_ms"])
        assert s["latency_ms"]["p50"] <= s["latency_ms"]["p95"] \
            <= s["latency_ms"]["p99"]
        # per-row-bucket reservoirs (telemetry PR): the 3-row request
        # dispatched into the 4-bucket; only exercised buckets appear
        assert set(s["latency_ms_by_bucket"]) == {4}
        assert {"p50", "p95", "p99"} <= set(s["latency_ms_by_bucket"][4])
        assert 0 < s["mean_batch_occupancy"] <= 1.0
        assert s["throughput_rps"] > 0
        svc.stop()

    def test_zero_knobs_rejected_not_defaulted(self):
        """Regression: an explicit 0 must hit the batcher's >= 1
        validation, not silently fall through to the config default."""
        with pytest.raises(ValueError, match="max_batch_size"):
            InferenceService(make_model(), max_batch_size=0, start=False)
        with pytest.raises(ValueError, match="queue_capacity"):
            InferenceService(make_model(), queue_capacity=0, start=False)

    def test_dropped_service_stops_batcher_thread(self):
        """Regression: a service dropped without stop() (every
        historical PredictionService caller) must not strand its
        batcher thread for the life of the process."""
        import gc
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=2)
        batcher = svc._batcher
        assert batcher.running
        del svc
        gc.collect()
        assert not batcher.running

    def test_zero_timeout_is_adaptive_batching(self):
        """timeout 0: a lone request dispatches without waiting out a
        coalescing window, but a staged backlog still coalesces."""
        svc = InferenceService(make_model(), input_spec=SPEC16,
                               max_batch_size=8, batch_timeout_ms=0.0,
                               start=False)
        x = rows(np.random.default_rng(14), 1)
        futs = [svc.submit(x) for _ in range(8)]
        svc.start()
        for f in futs:
            assert f.result(timeout=60).shape == (1, 4)
        assert svc.stats()["dispatch_count"] == 1  # still coalesces
        svc.stop()

    def test_latency_reservoir_percentiles(self):
        r = LatencyReservoir(capacity=64)
        for v in range(1, 101):  # ring keeps the last 64: 37..100
            r.record(v / 1000.0)
        p = r.percentiles()
        assert p["p50"] <= p["p95"] <= p["p99"] <= p["max"]
        assert p["max"] == 100 / 1000.0


class TestModelRegistry:
    def test_versioning_and_routing(self):
        reg = ModelRegistry()
        m1, m2 = make_model(), make_model(dout=3)
        reg.deploy("clf", m1, input_spec=SPEC16, max_batch_size=4)
        reg.deploy("clf", m2, input_spec=SPEC16, max_batch_size=4)
        assert reg.list_models() == {"clf": [1, 2]}
        x = rows(np.random.default_rng(0), 2)
        assert reg.predict("clf", x, timeout=60).shape == (2, 3)  # latest
        assert reg.predict("clf", x, version=1, timeout=60).shape == (2, 4)
        reg.undeploy("clf", version=2)
        assert reg.predict("clf", x, timeout=60).shape == (2, 4)  # back to v1
        with pytest.raises(KeyError):
            reg.get("clf", version=2)
        reg.stop_all()
        with pytest.raises(KeyError):
            reg.get("clf")

    def test_duplicate_version_and_unknown_name(self):
        reg = ModelRegistry()
        reg.deploy("m", make_model(), version=7, input_spec=SPEC16)
        with pytest.raises(ValueError, match="already deployed"):
            reg.deploy("m", make_model(), version=7)
        with pytest.raises(KeyError, match="no model"):
            reg.get("ghost")
        reg.stop_all()

    def test_quantized_deploy(self):
        reg = ModelRegistry()
        svc = reg.deploy("q", make_model(), quantize=True,
                         input_spec=SPEC16, max_batch_size=4)
        x = rows(np.random.default_rng(1), 3)
        out = reg.predict("q", x, timeout=60)
        assert out.shape == (3, 4)
        direct, _ = svc.model.apply(svc.params, svc.state, x,
                                    training=False)
        np.testing.assert_array_equal(out, np.asarray(direct))
        reg.stop_all()

    def test_deploy_from_bigdl_wire_format(self, tmp_path):
        from bigdl_tpu.interop import save_bigdl_module
        path = str(tmp_path / "model.bigdl")
        save_bigdl_module(make_model(), path)
        reg = ModelRegistry()
        reg.deploy("wire", path=path, format="bigdl", input_spec=SPEC16,
                   max_batch_size=4)
        assert reg.predict(
            "wire", rows(np.random.default_rng(2), 2),
            timeout=60).shape == (2, 4)
        reg.stop_all()

    def test_concurrent_deploys_get_distinct_versions(self):
        """Regression: deploy reserves its (name, version) key before
        the slow AOT warmup, so concurrent auto-versioned deploys can't
        collide and orphan a service's batcher thread."""
        reg = ModelRegistry()
        barrier = threading.Barrier(4)
        results, errs = [], []

        def worker():
            barrier.wait()
            try:
                results.append(reg.deploy("race", make_model(),
                                          input_spec=SPEC16,
                                          max_batch_size=2))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs and len(results) == 4
        assert reg.list_models() == {"race": [1, 2, 3, 4]}
        # every returned service is routable (none orphaned)
        routable = {id(reg.get("race", version=v)) for v in (1, 2, 3, 4)}
        assert routable == {id(s) for s in results}
        reg.stop_all()

    def test_registry_stats(self):
        reg = ModelRegistry()
        reg.deploy("a", make_model(), input_spec=SPEC16)
        reg.deploy("b", make_model(), input_spec=SPEC16)
        reg.predict("a", rows(np.random.default_rng(3), 1), timeout=60)
        stats = reg.stats()
        assert set(stats) == {"a:v1", "b:v1"}
        assert stats["a:v1"]["requests_completed"] == 1
        reg.stop_all()


class TestPredictorSatellites:
    def test_partial_tail_batch_single_compile(self):
        """GL106 regression: the trailing partial batch must reuse the
        steady-state executable (zero-pad + slice), not compile a second
        shape.  Gated on the jit's REAL compile-cache size (eval_shape
        probes trace but never compile, so a wrapped-fn trace counter
        would over-count)."""
        model = make_model(din=4, dout=3)
        pred = Predictor(model, batch_size=4)
        from bigdl_tpu.dataset.sample import Sample
        rng = np.random.default_rng(0)
        samples = [Sample(rng.normal(0, 1, (4,)).astype(np.float32))
                   for _ in range(10)]  # 4 + 4 + 2-row tail
        out = pred.predict(samples)
        assert out.shape == (10, 3)
        assert pred._fwd._cache_size() == 1, (
            f"expected ONE compiled executable for the whole dataset, "
            f"got {pred._fwd._cache_size()} (tail batch recompiled)")

    def test_partial_tail_rows_exact(self):
        model = make_model(din=4, dout=3)
        pred = Predictor(model, batch_size=4)
        from bigdl_tpu.dataset.sample import Sample
        rng = np.random.default_rng(1)
        xs = rng.normal(0, 1, (6, 4)).astype(np.float32)
        out = pred.predict([Sample(x) for x in xs])
        direct, _ = model.apply(pred.params, pred.state, xs,
                                training=False)
        np.testing.assert_allclose(out, np.asarray(direct), rtol=1e-6,
                                    atol=1e-7)

    def test_sparse_mixed_leading_dims_fall_back_to_legacy(self):
        """Regression: SparseMiniBatch-style inputs — (ids(nnz), dense(N))
        leaves with DIFFERENT leading dims — must dispatch as-is (no row
        accounting), exactly like the pre-PR Predictor."""
        from bigdl_tpu.dataset.dataset import AbstractDataSet
        from bigdl_tpu.dataset.sample import MiniBatch

        class BagModel(Module):
            """Embedding-bag + dense tower: input (flat_ids(nnz),
            seg(nnz), dense(N, 2)) -> (N, 3)."""

            def init(self, rng):
                k1, k2 = jax.random.split(rng)
                return {"emb": jax.random.normal(k1, (10, 3)),
                        "w": jax.random.normal(k2, (2, 3))}, {}

            def apply(self, params, state, input, *, training=False,
                      rng=None):
                ids, seg, dense = input
                bags = jax.ops.segment_sum(
                    params["emb"][ids], seg,
                    num_segments=dense.shape[0])
                return bags + dense @ params["w"], state

        class FakeDS(AbstractDataSet):
            def __init__(self, batches):
                self.batches = batches

            def data(self, train=False):
                return iter(self.batches)

            def size(self):
                return sum(b.size() for b in self.batches)

        rng = np.random.default_rng(17)
        batches, expect = [], []
        model = BagModel().initialize(0)
        for n, nnz in ((4, 9), (4, 5)):  # second batch: smaller nnz
            ids = rng.integers(0, 10, nnz).astype(np.int32)
            seg = np.sort(rng.integers(0, n, nnz)).astype(np.int32)
            dense = rng.normal(0, 1, (n, 2)).astype(np.float32)
            batches.append(MiniBatch((ids, seg, dense)))
            out, _ = model.apply(model._params, model._state,
                                 (ids, seg, dense), training=False)
            expect.append(np.asarray(out))
        got = Predictor(model).predict(FakeDS(batches))
        np.testing.assert_array_equal(got, np.concatenate(expect, axis=0))

    def test_coo_nnz_coincidence_keeps_all_rows(self):
        """Regression (confirmed repro in review): COO-only batches
        whose FIRST nnz bucket coincides with the sample count must not
        have real output rows sliced away when a later batch's nnz is
        smaller — the two-point eval_shape probe detects that output
        rows come from static metadata, and the tail dispatches
        unpadded."""
        from bigdl_tpu.dataset.dataset import AbstractDataSet
        from bigdl_tpu.dataset.sample import MiniBatch

        N = 8

        class StaticBag(Module):
            """(ids(nnz), seg(nnz)) -> (8, 3): output rows are a static
            constant, NOT the input leading dim."""

            def init(self, rng):
                return {"emb": jax.random.normal(rng, (10, 3))}, {}

            def apply(self, params, state, input, *, training=False,
                      rng=None):
                ids, seg = input
                return jax.ops.segment_sum(params["emb"][ids], seg,
                                           num_segments=N), state

        class FakeDS(AbstractDataSet):
            def __init__(self, batches):
                self.batches = batches

            def data(self, train=False):
                return iter(self.batches)

            def size(self):
                return N * len(self.batches)

        rng = np.random.default_rng(18)
        model = StaticBag().initialize(0)
        batches, expect = [], []
        for nnz in (N, 4):  # first batch nnz == N: the coincidence
            ids = rng.integers(0, 10, nnz).astype(np.int32)
            seg = np.sort(rng.integers(0, N, nnz)).astype(np.int32)
            batches.append(MiniBatch((ids, seg)))
            out, _ = model.apply(model._params, model._state, (ids, seg),
                                 training=False)
            expect.append(np.asarray(out))
        got = Predictor(model).predict(FakeDS(batches))
        assert got.shape == (2 * N, 3), got.shape
        np.testing.assert_array_equal(got,
                                      np.concatenate(expect, axis=0))

    def test_empty_iterable_output_rank(self):
        model = make_model(din=4, dout=3)
        pred = Predictor(model, batch_size=4,
                         input_spec=((4,), np.float32))
        out = pred.predict([])
        assert out.shape == (0, 3) and out.dtype == np.float32
        # without a spec the legacy rank-less fallback survives
        assert Predictor(model, batch_size=4).predict([]).shape == (0,)


class TestPredictionServiceShim:
    def test_back_compat_surface(self):
        svc = PredictionService(make_model(), batch_size=4)
        out1 = svc.predict(np.ones((1, 16), np.float32))
        out9 = svc.predict(np.ones((9, 16), np.float32))
        assert out1.shape == (1, 4) and out9.shape == (9, 4)
        np.testing.assert_allclose(out9[0], out1[0], rtol=1e-6)
        assert svc.request_count == 2
        stats = svc.stats()
        assert stats["model"] == "PredictionService"
        assert stats["dispatch_count"] >= 1
        # the shim keeps its historical lone-caller latency: adaptive
        # mode, no coalescing-timeout tax on sequential predicts
        assert svc.service.batch_timeout_ms == 0.0
        svc.stop()

    def test_shim_accepts_list_of_lists(self):
        """Regression: the historical service np.asarray'd its input, so
        plain nested lists must keep working through the shim."""
        svc = PredictionService(make_model(din=4), batch_size=4)
        out = svc.predict([[1.0, 2.0, 3.0, 4.0],
                           [5.0, 6.0, 7.0, 8.0]])
        assert out.shape == (2, 4)
        svc.stop()

    def test_shim_coalesces_concurrent_callers(self):
        model = make_model()
        svc = PredictionService(model, batch_size=8,
                                batch_timeout_ms=1.0)
        rng = np.random.default_rng(4)
        xs = [rows(rng, 1) for _ in range(12)]
        errs = []

        def worker(x):
            try:
                out = svc.predict(x)
                direct, _ = model.apply(svc.params, svc.state, x,
                                        training=False)
                np.testing.assert_array_equal(out, np.asarray(direct))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(x,)) for x in xs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert svc.request_count == 12
        svc.stop()
