"""Sharded serving + continuous-batching decode (ISSUE 20).

The load-bearing gates:

- **Decode correctness**: the KV-cache prefill is BITWISE-equal to the
  full-context ``model.apply`` (same shapes ⇒ same XLA reduction
  order), and every incremental decode step is tight-allclose
  (rtol=1e-5, atol=1e-6) to a full-context forward over the grown
  sequence — the PR-16 cross-shape numerics precedent: the step's
  attention GEMMs run at Tq=1 vs the reference's Tq=T, so reduction
  order differs while greedy argmax tokens stay EXACTLY equal.
  Covered at every step, including mid-batch admission and
  slot-reuse-after-EOS.
- **Continuous batching, proven by accounting**: a sequence submitted
  while another is mid-decode joins the RUNNING batch —
  ``A.admit_step <= B.admit_step < A.finish_step`` on the
  ``DecodeResult`` step counters (dispatch accounting, never timing).
- **Sharded replicas**: a ``ShardedReplicaSet`` slot owns an N-device
  mesh slice with ``param_specs``-declared NamedShardings; it serves
  through the unchanged ``FrontendServer`` submit() contract.
- **Wire generate route (both cores)**: chunked-ndjson token streams
  arrive in order and equal the per-request full-context reference;
  zero dropped requests through one ``HotCutover`` over a
  ``deploy(service=)`` decode backend.
- **Chunked request bodies (both cores)**: ``Transfer-Encoding:
  chunked`` POSTs are de-chunked incrementally by the shared
  ``ChunkedDecoder``; malformed framing answers 400, the body cap
  413, TE+CL smuggling 400, unknown codings 501.

Tiny models throughout; no benchmark cell serves yet (PERF.md 7b).
"""

import http.client
import json
import socket
import threading
import time
from io import BytesIO

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.frontend import FrontendServer, HotCutover
from bigdl_tpu.frontend.http1 import (ChunkedDecoder, ProtocolError,
                                      RequestParser, read_chunked_body)
from bigdl_tpu.models.transformer import (init_kv_cache, kv_cache_spec,
                                          transformer_lm,
                                          transformer_lm_decode_step,
                                          transformer_lm_prefill)
from bigdl_tpu.serving import (DeadlineExceeded, DecodeService,
                               InferenceService, ModelRegistry,
                               RequestSpecError, ServiceClosed,
                               ServiceOverloaded, ShardedReplicaSet)

VOCAB = 64


@pytest.fixture(scope="module")
def lm():
    return transformer_lm(vocab_size=VOCAB, embed_dim=32, num_heads=4,
                          num_layers=2, max_len=64).initialize(0)


def greedy_ref(model, prompt, max_new, eos_id=None, max_seq_len=64):
    """Per-request full-context greedy reference: re-run the WHOLE
    grown sequence through ``model.apply`` for every next token —
    exactly what the KV-cache path must reproduce."""
    toks = [int(t) for t in prompt]
    max_new = min(int(max_new), max_seq_len - len(toks))
    out = []
    for _ in range(max_new):
        lp, _ = model.apply(model._params, model._state,
                            np.asarray([toks], np.int32),
                            training=False)
        nxt = int(np.asarray(lp)[0, -1].argmax())
        out.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
        toks.append(nxt)
        if len(toks) >= max_seq_len:
            break
    return out


def wait_until(pred, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.005)


# ===========================================================================
# decode-path numerics — pure functions, no threads (satellite 3)
# ===========================================================================
class TestDecodeNumerics:
    def test_prefill_bitwise_equals_full_context(self, lm):
        """Prefill runs the same (S, T) shapes as the full-context
        apply, so XLA's reduction order matches and equality is
        BITWISE — the strongest half of the correctness gate."""
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, VOCAB, (1, 12)).astype(np.int32)
        ref, _ = lm.apply(lm._params, lm._state, prompt, training=False)
        lp, k, v = transformer_lm_prefill(lm, lm._params,
                                          jnp.asarray(prompt))
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(ref))
        shape, _ = kv_cache_spec(lm, 1, 12)
        assert k.shape == shape and v.shape == shape

    def test_incremental_steps_allclose_full_context_every_step(
            self, lm):
        """Every decode step's logits vs a full-context forward over
        the grown sequence: tight-allclose (rtol=1e-5, atol=1e-6 —
        measured ≲5e-7; NOT bitwise because the step attends Tq=1
        against the cache while the reference runs Tq=T, so the
        attention GEMM reduction order differs), and greedy argmax
        tokens EXACTLY equal."""
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, VOCAB, (1, 9)).astype(np.int32)
        lp, kp, vp = transformer_lm_prefill(lm, lm._params,
                                            jnp.asarray(prompt))
        k, v = init_kv_cache(lm, 1, 64)
        k = jax.lax.dynamic_update_slice(k, kp, (0, 0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vp, (0, 0, 0, 0, 0))
        toks = list(prompt[0])
        last = int(np.asarray(lp)[0, -1].argmax())
        lengths = np.array([9], np.int32)
        for _ in range(8):
            toks.append(last)
            lp1, k, v = transformer_lm_decode_step(
                lm, lm._params, jnp.asarray([last], jnp.int32),
                jnp.asarray(lengths), k, v)
            lengths[0] += 1
            ref, _ = lm.apply(lm._params, lm._state,
                              np.asarray([toks], np.int32),
                              training=False)
            got = np.asarray(lp1)[0]
            want = np.asarray(ref)[0, -1]
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
            assert int(got.argmax()) == int(want.argmax())
            last = int(got.argmax())

    def test_mid_batch_admission_numerics(self, lm):
        """Admitting B into slot 1 while A is mid-decode in slot 0 must
        not perturb either sequence: after the splice, EVERY further
        step matches both sequences' own full-context references."""
        rng = np.random.default_rng(2)
        pa = rng.integers(0, VOCAB, (7,)).astype(np.int32)
        pb = rng.integers(0, VOCAB, (4,)).astype(np.int32)
        k, v = init_kv_cache(lm, 2, 64)
        # prefill A into slot 0, step it alone three times
        lp, kp, vp = transformer_lm_prefill(lm, lm._params,
                                            jnp.asarray(pa[None, :]))
        k = jax.lax.dynamic_update_slice(k, kp, (0, 0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vp, (0, 0, 0, 0, 0))
        toks_a = list(pa)
        last = np.zeros((2,), np.int32)
        lengths = np.array([7, 0], np.int32)
        last[0] = int(np.asarray(lp)[0, -1].argmax())
        for _ in range(3):
            toks_a.append(int(last[0]))
            lp1, k, v = transformer_lm_decode_step(
                lm, lm._params, jnp.asarray(last),
                jnp.asarray(lengths), k, v)
            lengths[0] += 1
            last[0] = int(np.asarray(lp1)[0].argmax())
        # mid-batch: splice B's prefill into slot 1
        lpb, kb, vb = transformer_lm_prefill(lm, lm._params,
                                             jnp.asarray(pb[None, :]))
        k = jax.lax.dynamic_update_slice(k, kb, (0, 1, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vb, (0, 1, 0, 0, 0))
        toks_b = list(pb)
        lengths[1] = 4
        last[1] = int(np.asarray(lpb)[0, -1].argmax())
        for _ in range(4):
            toks_a.append(int(last[0]))
            toks_b.append(int(last[1]))
            lp1, k, v = transformer_lm_decode_step(
                lm, lm._params, jnp.asarray(last),
                jnp.asarray(lengths), k, v)
            lengths += 1
            lph = np.asarray(lp1)
            for slot, toks in ((0, toks_a), (1, toks_b)):
                ref, _ = lm.apply(lm._params, lm._state,
                                  np.asarray([toks], np.int32),
                                  training=False)
                want = np.asarray(ref)[0, -1]
                np.testing.assert_allclose(lph[slot], want,
                                           rtol=1e-5, atol=1e-6)
                assert int(lph[slot].argmax()) == int(want.argmax())
                last[slot] = int(lph[slot].argmax())

    def test_slot_reuse_overwrites_stale_cache(self, lm):
        """Re-prefilling a slot after a finished sequence must fully
        mask the previous occupant: the new sequence decodes exactly
        as if the cache had been zeroed (stale positions past the new
        length are never attended)."""
        rng = np.random.default_rng(3)
        pa = rng.integers(0, VOCAB, (11,)).astype(np.int32)
        pb = rng.integers(0, VOCAB, (5,)).astype(np.int32)
        k, v = init_kv_cache(lm, 1, 64)
        _, kp, vp = transformer_lm_prefill(lm, lm._params,
                                           jnp.asarray(pa[None, :]))
        k = jax.lax.dynamic_update_slice(k, kp, (0, 0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vp, (0, 0, 0, 0, 0))
        # slot reclaimed; B (shorter!) takes it — A's tail positions
        # 5..10 still hold A's K/V
        lpb, kb, vb = transformer_lm_prefill(lm, lm._params,
                                             jnp.asarray(pb[None, :]))
        k = jax.lax.dynamic_update_slice(k, kb, (0, 0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(v, vb, (0, 0, 0, 0, 0))
        toks = list(pb)
        last = int(np.asarray(lpb)[0, -1].argmax())
        lengths = np.array([5], np.int32)
        for _ in range(6):
            toks.append(last)
            lp1, k, v = transformer_lm_decode_step(
                lm, lm._params, jnp.asarray([last], jnp.int32),
                jnp.asarray(lengths), k, v)
            lengths[0] += 1
            ref, _ = lm.apply(lm._params, lm._state,
                              np.asarray([toks], np.int32),
                              training=False)
            got = np.asarray(lp1)[0]
            want = np.asarray(ref)[0, -1]
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
            assert int(got.argmax()) == int(want.argmax())
            last = int(got.argmax())


# ===========================================================================
# DecodeService — the continuous-batching scheduler
# ===========================================================================
class TestDecodeService:
    def test_single_request_equals_reference(self, lm):
        with DecodeService(lm, slots=2, max_seq_len=48,
                           max_prompt_len=8, prefill_buckets="top",
                           name="d1") as dec:
            prompt = [5, 9, 3]
            res = dec.generate(prompt, max_new_tokens=6)
        ref = greedy_ref(lm, prompt, 6, max_seq_len=48)
        assert list(res.tokens) == ref
        assert res.finish_reason == "length"
        assert res.prompt_len == 3 and res.prefill_bucket >= 3
        assert res.admit_step <= res.finish_step

    def test_concurrent_mixed_lengths_equal_reference(self, lm):
        """The acceptance shape: staged concurrent requests of
        DIFFERENT lengths all resolve token-for-token equal to their
        own full-context references — zero drops."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, VOCAB, (n,)).tolist()
                   for n in (2, 5, 9, 14, 3, 7)]
        with DecodeService(lm, slots=3, max_seq_len=48,
                           max_prompt_len=16, prefill_buckets="top",
                           name="dmix") as dec:
            futs = [dec.submit(p, max_new_tokens=4 + i % 3)
                    for i, p in enumerate(prompts)]
            results = [f.result(timeout=120) for f in futs]
        for i, (p, res) in enumerate(zip(prompts, results)):
            ref = greedy_ref(lm, p, 4 + i % 3, max_seq_len=48)
            assert list(res.tokens) == ref, f"request {i}"
        occupied = {r.slot for r in results}
        assert occupied <= set(range(3))

    def test_mid_batch_admission_by_step_accounting(self, lm):
        """THE continuous-batching gate, by dispatch accounting rather
        than timing: B is submitted from inside A's on_token callback
        (so A is demonstrably mid-decode), and B's result must show it
        joined A's RUNNING batch — ``A.admit_step <= B.admit_step <
        A.finish_step`` — while both stay token-correct."""
        fut_b = []
        dec = DecodeService(lm, slots=2, max_seq_len=48,
                            max_prompt_len=8, prefill_buckets="top",
                            name="dmid")

        def on_token(index, token):
            if index == 2 and not fut_b:
                fut_b.append(dec.submit([11, 2], max_new_tokens=3))

        try:
            fut_a = dec.submit([5, 9, 3, 1], max_new_tokens=12,
                               on_token=on_token)
            res_a = fut_a.result(timeout=120)
            assert fut_b, "on_token never fired at index 2"
            res_b = fut_b[0].result(timeout=120)
        finally:
            dec.stop()
        assert list(res_a.tokens) == greedy_ref(lm, [5, 9, 3, 1], 12,
                                                max_seq_len=48)
        assert list(res_b.tokens) == greedy_ref(lm, [11, 2], 3,
                                                max_seq_len=48)
        assert res_a.admit_step <= res_b.admit_step < res_a.finish_step
        assert res_a.slot != res_b.slot  # genuinely concurrent slots

    def test_on_token_streams_every_token_in_order(self, lm):
        seen = []
        with DecodeService(lm, slots=1, max_seq_len=48,
                           max_prompt_len=8, prefill_buckets="top",
                           name="dstr") as dec:
            res = dec.generate([5, 9, 3], max_new_tokens=5,
                               on_token=lambda i, t: seen.append((i, t)))
        assert [i for i, _ in seen] == list(range(len(res.tokens)))
        assert [t for _, t in seen] == list(res.tokens)

    def test_slot_reuse_after_eos(self, lm):
        """EOS mid-generation reclaims the slot THAT step and the next
        queued sequence takes it; the reused slot decodes its new
        occupant exactly (stale cache fully masked)."""
        ref = greedy_ref(lm, [5, 9, 3], 10, max_seq_len=48)
        # an eos that fires MID-generation: the first token whose first
        # occurrence in the reference stream is at index >= 1
        eos = next(t for i, t in enumerate(ref)
                   if ref.index(t) == i and i >= 1)
        k = ref.index(eos)
        ref_eos = greedy_ref(lm, [5, 9, 3], 10, eos_id=eos,
                             max_seq_len=48)
        assert ref_eos == ref[:k + 1] and len(ref_eos) >= 2
        with DecodeService(lm, slots=1, max_seq_len=48, eos_id=eos,
                           max_prompt_len=8, prefill_buckets="top",
                           name="deos") as dec:
            fut_a = dec.submit([5, 9, 3], max_new_tokens=10)
            fut_b = dec.submit([7, 1, 4, 2], max_new_tokens=4)
            res_a = fut_a.result(timeout=120)
            res_b = fut_b.result(timeout=120)
        assert res_a.finish_reason == "eos"
        assert list(res_a.tokens) == ref_eos
        assert res_b.slot == res_a.slot  # slots=1 ⇒ the SAME slot
        assert res_b.admit_step >= res_a.finish_step
        assert list(res_b.tokens) == greedy_ref(
            lm, [7, 1, 4, 2], 4, eos_id=eos, max_seq_len=48)
        st = dec.stats()["decode"]
        assert st["slots_reclaimed"] >= 2
        assert st["admissions"] == 2

    def test_request_spec_taxonomy(self, lm):
        with DecodeService(lm, slots=1, max_seq_len=32,
                           max_prompt_len=8, prefill_buckets="top",
                           name="dspec") as dec:
            with pytest.raises(RequestSpecError):
                dec.submit([[1, 2], [3, 4]])  # 2-D
            with pytest.raises(RequestSpecError):
                dec.submit([])  # empty
            with pytest.raises(RequestSpecError):
                dec.submit([1.5, 2.5])  # float tokens
            with pytest.raises(RequestSpecError):
                dec.submit(list(range(40)))  # > max_prompt_len
            with pytest.raises(RequestSpecError):
                dec.submit([1, 2], max_new_tokens=0)

    def test_expired_deadline_settles_deadline_exceeded(self, lm):
        with DecodeService(lm, slots=1, max_seq_len=16,
                           max_prompt_len=4, prefill_buckets="top",
                           name="ddl") as dec:
            fut = dec.submit([1, 2, 3],
                             deadline=time.monotonic() - 0.001)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)

    def test_overload_sheds_with_service_overloaded(self, lm):
        dec = DecodeService(lm, slots=1, max_seq_len=16,
                            max_prompt_len=4, prefill_buckets="top",
                            queue_capacity=2, name="dover",
                            start=False)  # never drains: queue fills
        try:
            dec.submit([1, 2])
            dec.submit([3, 4])
            with pytest.raises(ServiceOverloaded):
                dec.submit([5, 6])
        finally:
            dec.stop(drain=False)

    def test_stop_then_submit_service_closed(self, lm):
        dec = DecodeService(lm, slots=1, max_seq_len=16,
                            max_prompt_len=4, prefill_buckets="top",
                            name="dcl")
        dec.stop()
        with pytest.raises(ServiceClosed):
            dec.submit([1, 2])

    def test_nondrain_stop_cancels_backlog_and_active(self, lm):
        """Deterministically parked: A's on_token blocks the scheduler
        thread mid-admission, so B is still queued and A still active
        when the non-drain stop lands — A fails, B is cancelled, both
        with ServiceClosed."""
        dec = DecodeService(lm, slots=1, max_seq_len=16,
                            max_prompt_len=4, prefill_buckets="top",
                            name="dnd")
        entered, release = threading.Event(), threading.Event()

        def park(index, token):
            entered.set()
            release.wait(30)

        try:
            fut_a = dec.submit([1, 2], max_new_tokens=8, on_token=park)
            assert entered.wait(30)
            fut_b = dec.submit([3, 4])
            dec.stop(drain=False, timeout=0.01)  # returns immediately
            release.set()
            with pytest.raises(ServiceClosed):
                fut_a.result(timeout=60)
            with pytest.raises(ServiceClosed):
                fut_b.result(timeout=60)
        finally:
            release.set()
            dec.stop(drain=False)

    def test_zero_steady_state_retrace(self, lm):
        """The GL106 discipline at serving runtime: after construction
        warms every bucket + the step executable, NO request shape may
        trace again."""
        with DecodeService(lm, slots=2, max_seq_len=48,
                           max_prompt_len=16, prefill_buckets="pow2@4",
                           name="dtrace") as dec:
            warm = dec._trace_count
            assert warm > 0
            for n in (1, 3, 4, 7, 12):
                dec.generate(list(range(1, n + 1)), max_new_tokens=3)
            assert dec._trace_count == warm

    def test_kv_budget_is_a_hard_cap(self, lm):
        shape, dtype = kv_cache_spec(lm, 1, 32)
        per_slot_mb = (2 * int(np.prod(shape))
                       * jnp.dtype(dtype).itemsize) / (1 << 20)
        dec = DecodeService(lm, slots=8, max_seq_len=32,
                            max_prompt_len=4, prefill_buckets="top",
                            kv_budget_mb=per_slot_mb * 2.5,
                            name="dkv", start=False)
        assert dec.slots == 2  # 8 requested, budget affords 2
        assert dec.kv_bytes <= per_slot_mb * 2.5 * (1 << 20)
        dec.stop(drain=False)
        with pytest.raises(ValueError):
            DecodeService(lm, slots=1, max_seq_len=32,
                          max_prompt_len=4, prefill_buckets="top",
                          kv_budget_mb=per_slot_mb * 0.4, start=False)

    def test_stats_schema(self, lm):
        with DecodeService(lm, slots=2, max_seq_len=32,
                           max_prompt_len=4, prefill_buckets="top",
                           name="dst") as dec:
            dec.generate([1, 2, 3], max_new_tokens=4)
            st = dec.stats()
        d = st["decode"]
        assert d["slots"] == 2 and d["active"] == 0
        assert d["steps"] >= 3 and d["tokens_generated"] >= 4
        assert d["admissions"] == 1 and d["slots_reclaimed"] == 1
        assert 0.0 < d["step_occupancy"] <= 1.0
        assert d["kv_bytes"] > 0 and d["prefill_buckets"]
        assert st["requests_completed"] == 1

    def test_scheduler_crash_settles_inflight_futures(self, lm):
        # a crashed scheduler must fail every live future with the
        # crash (not park callers forever) and refuse new submits
        dec = DecodeService(lm, slots=2, max_seq_len=16,
                            max_prompt_len=4, prefill_buckets="top",
                            name="crash")
        try:
            dec._step_exec = _raise_injected
            fut = dec.submit([5, 9, 3], max_new_tokens=4)
            with pytest.raises(RuntimeError, match="injected step"):
                fut.result(timeout=30)
            wait_until(lambda: not dec.alive)
            with pytest.raises(ServiceClosed):
                dec.submit([1, 2])
        finally:
            dec.stop(drain=False, timeout=5)


def _raise_injected(*a, **kw):
    raise RuntimeError("injected step failure")


# ===========================================================================
# ShardedReplicaSet — mesh-slice replicas (tentpole part a)
# ===========================================================================
def make_mlp(din=16, dout=4, shard=False):
    return nn.Sequential(
        nn.Linear(din, 32, shard="column" if shard else None),
        nn.ReLU(),
        nn.Linear(32, dout, shard="row" if shard else None),
        nn.SoftMax()).initialize(0)


SPEC16 = ((16,), np.float32)


class TestShardedReplicaSet:
    def test_validation(self, devices):
        model = make_mlp()
        with pytest.raises(ValueError):
            ShardedReplicaSet(model, devices_per_replica=0)
        with pytest.raises(ValueError):
            ShardedReplicaSet(model, devices_per_replica=16)  # > 8 devs
        with pytest.raises(ValueError):
            ShardedReplicaSet(model, devices_per_replica=4,
                              mesh_axes={"bogus": 4})
        with pytest.raises(ValueError):
            ShardedReplicaSet(model, devices_per_replica=4,
                              mesh_axes={"model": 2})  # 2 != 4

    def test_params_land_with_declared_shardings(self, devices):
        """The tentpole's placement contract: a replica's params carry
        the module-declared NamedShardings over ITS mesh slice —
        column weight split P('model', None), row weight
        P(None, 'model'), non-opt-ins replicated."""
        from jax.sharding import PartitionSpec as P
        model = make_mlp(shard=True)
        rs = ShardedReplicaSet(model, devices_per_replica=4,
                               input_spec=SPEC16, start=False)
        try:
            assert rs.n_replicas == 2  # 8 devices / 4 per slice
            for ix in range(2):
                svc = rs._replicas[ix]
                mesh = rs.replica_mesh(ix)
                assert mesh.shape["model"] == 4
                w0 = svc.params["0"]["weight"]  # column Linear
                assert w0.sharding.spec == P("model", None)
                w2 = svc.params["2"]["weight"]  # row Linear
                assert w2.sharding.spec == P(None, "model")
                assert set(w0.sharding.mesh.devices.flat) == \
                    set(mesh.devices.flat)
            # the two slices own DISJOINT device groups
            d0 = set(rs.replica_mesh(0).devices.flat)
            d1 = set(rs.replica_mesh(1).devices.flat)
            assert d0.isdisjoint(d1)
        finally:
            rs.stop()

    def test_sharded_predict_equals_single_device(self, devices):
        model = make_mlp(shard=True)
        ref_model = make_mlp(shard=False)  # same init seed ⇒ same params
        rs = ShardedReplicaSet(model, devices_per_replica=4,
                               input_spec=SPEC16)
        try:
            x = np.random.default_rng(0).normal(
                0, 1, (6, 16)).astype(np.float32)
            got = np.asarray(rs.predict(x))
            ref, _ = ref_model.apply(ref_model._params,
                                     ref_model._state, x,
                                     training=False)
            np.testing.assert_allclose(got, np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
        finally:
            rs.stop()

    def test_serves_through_unchanged_frontend(self, devices):
        """Zero frontend changes: add_backend sees the submit()-shaped
        ReplicaSet contract and the wire path just works at mesh-slice
        granularity."""
        model = make_mlp(shard=True)
        rs = ShardedReplicaSet(model, devices_per_replica=2,
                               n_replicas=2, input_spec=SPEC16)
        reg = ModelRegistry()
        fe = FrontendServer(reg, port=0)
        fe.add_backend("shmlp", rs)
        fe.start()
        try:
            x = np.random.default_rng(1).normal(
                0, 1, (3, 16)).astype(np.float32)
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=60)
            conn.request("POST", "/v1/models/shmlp/predict",
                         body=json.dumps({"inputs": x.tolist()}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            assert resp.status == 200, body
            got = np.asarray(json.loads(body)["outputs"], np.float32)
            ref = np.asarray(rs.predict(x))
            np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
        finally:
            fe.stop()
            rs.stop()

    def test_elastic_resize_keeps_mesh_granularity(self, devices):
        model = make_mlp(shard=True)
        rs = ShardedReplicaSet(model, devices_per_replica=2,
                               n_replicas=1, input_spec=SPEC16)
        try:
            rs.set_replica_count(3)  # > 8//2 groups? no: 3 <= 4 groups
            assert rs.n_replicas == 3
            for ix in range(3):
                assert rs.replica_mesh(ix).shape["model"] == 2
            x = np.random.default_rng(2).normal(
                0, 1, (4, 16)).astype(np.float32)
            got = np.asarray(rs.predict(x))
            assert got.shape == (4, 4)
            st = rs.stats()
            assert len(st["replicas"]) == 3
        finally:
            rs.stop()

    def test_sharded_decode_service_equals_reference(self, lm, devices):
        """DecodeService(mesh=) — sharded big-model decode: params laid
        out by param_specs over a 4-device mesh, tokens still EXACTLY
        the unsharded greedy reference."""
        from bigdl_tpu.parallel.mesh import create_mesh
        sh = transformer_lm(vocab_size=VOCAB, embed_dim=32, num_heads=4,
                            num_layers=2, max_len=64,
                            shard=True).initialize(0)
        mesh = create_mesh(model=4, devices=jax.local_devices()[:4])
        with DecodeService(sh, slots=2, max_seq_len=16, mesh=mesh,
                           max_prompt_len=4, prefill_buckets="top",
                           name="dsh") as dec:
            res = dec.generate([5, 9, 3], max_new_tokens=4)
        # same init seed ⇒ same params ⇒ same greedy tokens as the
        # unsharded fixture model
        assert list(res.tokens) == greedy_ref(lm, [5, 9, 3], 4,
                                              max_seq_len=16)


# ===========================================================================
# wire generate route — both connection cores
# ===========================================================================
def post(port, path, body, headers=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def parse_stream(body: bytes):
    """ndjson token stream → (ordered token list, done trailer)."""
    lines = [json.loads(ln) for ln in body.splitlines()]
    assert lines, "empty stream"
    done = lines[-1]
    toks = lines[:-1]
    assert [t["index"] for t in toks] == list(range(len(toks)))
    return [t["token"] for t in toks], done


@pytest.fixture(scope="module")
def genstack(lm):
    reg = ModelRegistry()
    dec = DecodeService(lm, slots=3, max_seq_len=48, queue_capacity=64,
                        max_prompt_len=16, prefill_buckets="top",
                        name="lm")
    reg.deploy("lm", service=dec)
    clf = make_mlp()
    reg.deploy("clf", clf, input_spec=SPEC16, max_batch_size=8,
               batch_timeout_ms=2.0)
    yield reg, lm
    reg.stop_all()


@pytest.fixture(scope="module", params=["eventloop", "threaded"])
def genwire(request, genstack):
    reg, lm = genstack
    fe = FrontendServer(reg, port=0, core=request.param)
    fe.start()
    yield fe, reg, lm
    fe.stop()


class TestGenerateWire:
    def test_stream_ordered_and_equal_reference(self, genwire):
        fe, _reg, lm = genwire
        status, hdrs, body = post(
            fe.port, "/v1/models/lm/generate",
            json.dumps({"prompt": [5, 9, 3],
                        "max_new_tokens": 6}).encode())
        assert status == 200, body
        assert hdrs["Content-Type"] == "application/x-ndjson"
        assert hdrs.get("X-Trace-Id")
        streamed, done = parse_stream(body)
        ref = greedy_ref(lm, [5, 9, 3], 6, max_seq_len=48)
        assert done["done"] is True and done["finish_reason"] == "length"
        assert done["tokens"] == streamed == ref
        assert done["n"] == len(ref)

    def test_concurrent_mixed_lengths_zero_drops(self, genwire):
        """The wire acceptance gate: staged concurrent decode requests
        of different lengths all stream in order and equal their own
        references — zero dropped requests."""
        fe, _reg, lm = genwire
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, VOCAB, (n,)).tolist()
                   for n in (2, 6, 11, 4, 8, 3)]
        results = [None] * len(prompts)

        def client(i):
            results[i] = post(
                fe.port, "/v1/models/lm/generate",
                json.dumps({"prompt": prompts[i],
                            "max_new_tokens": 3 + i % 4}).encode())

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, p in enumerate(prompts):
            status, _h, body = results[i]
            assert status == 200, (i, body)
            streamed, done = parse_stream(body)
            ref = greedy_ref(lm, p, 3 + i % 4, max_seq_len=48)
            assert streamed == ref == done["tokens"], f"request {i}"

    def test_generate_on_predict_backend_400(self, genwire):
        fe, _reg, _lm = genwire
        status, _h, body = post(
            fe.port, "/v1/models/clf/generate",
            json.dumps({"prompt": [1, 2]}).encode())
        assert status == 400
        assert b"not a decode backend" in body

    def test_predict_on_decode_backend_400(self, genwire):
        fe, _reg, _lm = genwire
        status, _h, _body = post(
            fe.port, "/v1/models/lm/predict",
            json.dumps({"inputs": [[1.0, 2.0]]}).encode())
        assert status == 400

    def test_generate_body_taxonomy_400(self, genwire):
        fe, _reg, _lm = genwire
        for payload in (b"not json", b'{"inputs": [1]}',
                        b'{"prompt": []}', b'{"prompt": [[1, 2]]}',
                        b'{"prompt": [1], "max_new_tokens": 0}'):
            status, _h, _b = post(fe.port, "/v1/models/lm/generate",
                                  payload)
            assert status == 400, payload

    def test_unknown_model_404(self, genwire):
        fe, _reg, _lm = genwire
        status, _h, _b = post(fe.port, "/v1/models/nope/generate",
                              json.dumps({"prompt": [1]}).encode())
        assert status == 404

    def test_wire_deadline_while_queued_504(self, genwire):
        """A prompt still queued past its wire deadline answers 504 —
        the pre-stream path, so the REAL status goes out (no 200
        header committed).  Staged with a never-started service so
        expiry is deterministic."""
        fe, reg, lm = genwire
        parked = DecodeService(lm, slots=1, max_seq_len=16,
                               max_prompt_len=4, prefill_buckets="top",
                               name="parked", start=False)
        reg.deploy("parked", service=parked)
        try:
            status, _h, body = post(
                fe.port, "/v1/models/parked/generate",
                json.dumps({"prompt": [1, 2]}).encode(),
                headers={"X-Deadline-Ms": "120"})
            assert status == 504, body
        finally:
            reg.undeploy("parked", drain=False)

    def test_hot_cutover_zero_drops_under_generate_load(self, genwire):
        """One HotCutover over a deploy(service=) decode backend while
        12 concurrent generate clients stream: every request answers
        200 with reference-equal tokens (zero drops), the wire drains,
        and the outgoing service is stopped."""
        fe, _reg, lm = genwire
        reg2 = ModelRegistry()
        reg2.deploy("cut", service=DecodeService(
            lm, slots=3, max_seq_len=32, queue_capacity=64,
            max_prompt_len=8, prefill_buckets="top", name="cut-v1"))
        fe2 = FrontendServer(reg2, port=0, core=fe.core)
        fe2.start()
        cut = HotCutover(reg2, fe2)
        n = 12
        results = [None] * n
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, VOCAB, (2 + i % 5,)).tolist()
                   for i in range(n)]
        barrier = threading.Barrier(n + 1)

        def client(i):
            barrier.wait()
            time.sleep(0.01 * i)  # staged: spans the cutover window
            results[i] = post(
                fe2.port, "/v1/models/cut/generate",
                json.dumps({"prompt": prompts[i],
                            "max_new_tokens": 4}).encode())

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        barrier.wait()
        old = reg2.get("cut", reg2.latest_version("cut"))
        report = cut.deploy("cut", service=DecodeService(
            lm, slots=3, max_seq_len=32, queue_capacity=64,
            max_prompt_len=8, prefill_buckets="top", name="cut-v2"))
        for t in threads:
            t.join()
        try:
            assert report["old_undeployed"] is True
            assert report["wire_drained"] is True
            assert not old.alive  # outgoing service actually stopped
            for i in range(n):
                status, _h, body = results[i]
                assert status == 200, (i, body)
                streamed, done = parse_stream(body)
                ref = greedy_ref(lm, prompts[i], 4, max_seq_len=32)
                assert streamed == ref == done["tokens"], f"client {i}"
        finally:
            fe2.stop()
            reg2.stop_all()


# ===========================================================================
# chunked request bodies — shared decoder + both cores (satellite 1)
# ===========================================================================
def chunk_body(payload: bytes, sizes):
    """Encode ``payload`` as chunked transfer coding, cut at ``sizes``
    (any remainder becomes a final chunk)."""
    pieces, off = [], 0
    for n in sizes:
        pieces.append(payload[off:off + n])
        off += n
    pieces.append(payload[off:])
    out = b"".join(f"{len(p):x}\r\n".encode() + p + b"\r\n"
                   for p in pieces if p)
    return out + b"0\r\n\r\n"


def chunked_req(path, payload: bytes, sizes, extra=None):
    head = (f"POST {path} HTTP/1.1\r\n"
            "Host: t\r\n"
            "Content-Type: application/json\r\n"
            "Transfer-Encoding: chunked\r\n"
            + "".join(f"{k}: {v}\r\n" for k, v in (extra or {}).items())
            + "\r\n")
    return head.encode("latin-1") + chunk_body(payload, sizes)


class TestChunkedDecoder:
    def test_byte_at_a_time_roundtrip(self):
        payload = b'{"hello": "world", "n": 12345}'
        wire = chunk_body(payload, [3, 7, 1, 11])
        dec = ChunkedDecoder(1 << 20)
        for i in range(len(wire)):
            dec.feed(wire[i:i + 1])
            body = dec.poll()
            if body is not None:
                assert i == len(wire) - 1  # only the LAST byte completes
                assert body == payload
                break
        else:
            pytest.fail("decoder never completed")
        assert dec.residual() == b""

    def test_chunk_extensions_discarded(self):
        dec = ChunkedDecoder(1 << 20)
        dec.feed(b"5;ext=foo\r\nhello\r\n0\r\n\r\n")
        assert dec.poll() == b"hello"

    def test_trailer_fields_discarded(self):
        dec = ChunkedDecoder(1 << 20)
        dec.feed(b"2\r\nhi\r\n0\r\nX-Check: abc\r\nX-More: d\r\n\r\n")
        assert dec.poll() == b"hi"

    def test_residual_preserves_pipelined_bytes(self):
        dec = ChunkedDecoder(1 << 20)
        dec.feed(b"2\r\nok\r\n0\r\n\r\nGET / HTTP/1.1\r\n")
        assert dec.poll() == b"ok"
        assert dec.residual() == b"GET / HTTP/1.1\r\n"

    def test_malformed_size_line_400(self):
        dec = ChunkedDecoder(1 << 20)
        dec.feed(b"ZZZ\r\n")
        with pytest.raises(ProtocolError) as ei:
            dec.poll()
        assert ei.value.status == 400

    def test_missing_chunk_terminator_400(self):
        dec = ChunkedDecoder(1 << 20)
        dec.feed(b"2\r\nhiXX0\r\n\r\n")  # XX where CRLF belongs
        with pytest.raises(ProtocolError) as ei:
            dec.poll()
        assert ei.value.status == 400

    def test_body_cap_413(self):
        dec = ChunkedDecoder(16)
        dec.feed(b"20\r\n" + b"a" * 32 + b"\r\n0\r\n\r\n")
        with pytest.raises(ProtocolError) as ei:
            dec.poll()
        assert ei.value.status == 413

    def test_read_chunked_body_blocking_driver(self):
        payload = b"x" * 100
        rfile = BytesIO(chunk_body(payload, [40, 40]))
        assert read_chunked_body(rfile) == payload

    def test_read_chunked_body_truncated_400(self):
        rfile = BytesIO(b"10\r\nonly-seven")  # stream ends mid-chunk
        with pytest.raises(ProtocolError) as ei:
            read_chunked_body(rfile)
        assert ei.value.status == 400

    def test_read_chunked_body_cap_413(self):
        rfile = BytesIO(chunk_body(b"y" * 64, [64]))
        with pytest.raises(ProtocolError) as ei:
            read_chunked_body(rfile, max_body=16)
        assert ei.value.status == 413


class TestChunkedRequestParser:
    def test_chunked_request_end_to_end(self):
        payload = json.dumps({"inputs": [[1.0, 2.0]]}).encode()
        raw = chunked_req("/v1/models/clf/predict", payload, [5, 9])
        p = RequestParser()
        for i in range(len(raw)):
            p.feed(raw[i:i + 1])
            req = p.poll()
            if req is not None:
                assert i == len(raw) - 1
                assert req.body == payload
                return
        pytest.fail("parser never produced the request")

    def test_chunked_then_pipelined_keepalive_not_misframed(self):
        payload = b'{"a": 1}'
        raw = chunked_req("/a", payload, [4]) + \
            b"GET /b HTTP/1.1\r\nHost: t\r\n\r\n"
        p = RequestParser()
        p.feed(raw)
        ra = p.poll()
        assert ra is not None and ra.body == payload
        rb = p.poll()
        assert rb is not None and rb.target == "/b"

    def test_te_plus_content_length_400(self):
        p = RequestParser()
        p.feed(b"POST / HTTP/1.1\r\nContent-Length: 3\r\n"
               b"Transfer-Encoding: chunked\r\n\r\n")
        with pytest.raises(ProtocolError) as ei:
            p.poll()
        assert ei.value.status == 400  # request-smuggling refusal

    def test_unknown_transfer_coding_501(self):
        p = RequestParser()
        p.feed(b"POST / HTTP/1.1\r\nTransfer-Encoding: gzip\r\n\r\n")
        with pytest.raises(ProtocolError) as ei:
            p.poll()
        assert ei.value.status == 501

    def test_parser_max_body_cap_413(self):
        p = RequestParser(max_body=16)
        p.feed(b"POST / HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\n"
               + chunk_body(b"z" * 64, [64]))
        with pytest.raises(ProtocolError) as ei:
            p.poll()
        assert ei.value.status == 413


def post_chunked(port, path, payload: bytes, piece=7, timeout=120):
    """POST ``payload`` with ``Transfer-Encoding: chunked`` (http.client
    encodes each yielded piece as one chunk)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", path,
            body=(payload[i:i + piece]
                  for i in range(0, len(payload), piece)),
            headers={"Content-Type": "application/json",
                     "Transfer-Encoding": "chunked"},
            encode_chunked=True)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


class TestChunkedWireBothCores:
    """Chunked POST bodies over live sockets against BOTH cores."""

    def _raw_status(self, fe, raw, timeout=60.0):
        """Send raw bytes, return the response status line's code."""
        s = socket.create_connection(("127.0.0.1", fe.port),
                                     timeout=timeout)
        try:
            s.sendall(raw)
            s.settimeout(timeout)
            buf = b""
            while b"\r\n" not in buf:
                d = s.recv(4096)
                if not d:
                    break
                buf += d
            assert buf, "connection closed with no response"
            return int(buf.split(b" ", 2)[1])
        finally:
            s.close()

    def test_chunked_predict_equals_reference(self, genwire):
        fe, reg, _lm = genwire
        x = np.random.default_rng(3).normal(
            0, 1, (2, 16)).astype(np.float32)
        payload = json.dumps({"inputs": x.tolist()}).encode()
        status, _h, body = post_chunked(
            fe.port, "/v1/models/clf/predict", payload, piece=11)
        assert status == 200, body
        svc = reg.get("clf", reg.latest_version("clf"))
        got = np.asarray(json.loads(body)["outputs"], np.float32)
        ref = svc.predict(x)
        np.testing.assert_allclose(got, np.asarray(ref),
                                   rtol=1e-6, atol=1e-7)

    def test_chunked_generate_streams_tokens(self, genwire):
        fe, _reg, lm = genwire
        payload = json.dumps({"prompt": [5, 9, 3],
                              "max_new_tokens": 4}).encode()
        status, _h, body = post_chunked(
            fe.port, "/v1/models/lm/generate", payload, piece=5)
        assert status == 200, body
        streamed, done = parse_stream(body)
        ref = greedy_ref(lm, [5, 9, 3], 4, max_seq_len=48)
        assert streamed == ref == done["tokens"]

    def test_raw_socket_chunked_with_extension_and_trailer(
            self, genwire):
        """Hand-built framing the stdlib client never produces: chunk
        extensions and trailer fields must be discarded on the wire
        path too."""
        fe, _reg, lm = genwire
        payload = json.dumps({"prompt": [5, 9, 3],
                              "max_new_tokens": 2}).encode()
        head = (b"POST /v1/models/lm/generate HTTP/1.1\r\n"
                b"Host: t\r\nContent-Type: application/json\r\n"
                b"Connection: close\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n")
        mid = len(payload) // 2
        raw = (head
               + f"{mid:x};ext=1\r\n".encode() + payload[:mid] + b"\r\n"
               + f"{len(payload) - mid:x}\r\n".encode()
               + payload[mid:] + b"\r\n"
               + b"0\r\nX-Trailer: ignored\r\n\r\n")
        s = socket.create_connection(("127.0.0.1", fe.port), timeout=60)
        try:
            s.sendall(raw)
            s.settimeout(60)
            buf = b""
            while True:
                d = s.recv(65536)
                if not d:
                    break
                buf += d
        finally:
            s.close()
        assert b" 200 " in buf.split(b"\r\n", 1)[0]
        ref = greedy_ref(lm, [5, 9, 3], 2, max_seq_len=48)
        done = json.loads([ln for ln in buf.splitlines()
                           if b'"done"' in ln][-1])
        assert done["tokens"] == ref

    def test_malformed_chunk_framing_400(self, genwire):
        fe, _reg, _lm = genwire
        head = (b"POST /v1/models/clf/predict HTTP/1.1\r\n"
                b"Host: t\r\nContent-Type: application/json\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n")
        assert self._raw_status(fe, head + b"NOTHEX\r\n") == 400

    def test_te_plus_cl_smuggling_refused_400(self, genwire):
        fe, _reg, _lm = genwire
        raw = (b"POST /v1/models/clf/predict HTTP/1.1\r\n"
               b"Host: t\r\nContent-Type: application/json\r\n"
               b"Content-Length: 5\r\n"
               b"Transfer-Encoding: chunked\r\n\r\n"
               b"0\r\n\r\n")
        assert self._raw_status(fe, raw) == 400

    def test_unknown_coding_501(self, genwire):
        fe, _reg, _lm = genwire
        raw = (b"POST /v1/models/clf/predict HTTP/1.1\r\n"
               b"Host: t\r\nContent-Type: application/json\r\n"
               b"Transfer-Encoding: gzip\r\n\r\nxxxx")
        assert self._raw_status(fe, raw) == 501


# ===========================================================================
# event-loop shard CPU pinning (satellite 2)
# ===========================================================================
class TestPinCpus:
    def test_config_env_knob(self, monkeypatch):
        from bigdl_tpu.utils.config import Config
        monkeypatch.setenv("BIGDL_TPU_FRONTEND_PIN_CPUS", "1")
        assert Config.from_env().frontend_pin_cpus is True
        monkeypatch.delenv("BIGDL_TPU_FRONTEND_PIN_CPUS")
        assert Config.from_env().frontend_pin_cpus is False

    @pytest.mark.skipif(not hasattr(__import__("os"),
                                    "sched_setaffinity"),
                        reason="no sched_setaffinity on this platform")
    def test_each_loop_pins_to_one_cpu(self, monkeypatch):
        import os
        calls = []
        monkeypatch.setattr(
            os, "sched_setaffinity",
            lambda pid, mask: calls.append((pid, set(mask))))
        reg = ModelRegistry()
        fe = FrontendServer(reg, port=0, core="eventloop", shards=2,
                            pin_cpus=True)
        fe.start()
        try:
            wait_until(lambda: len(calls) >= 2, what="loops pinned")
            avail = sorted(os.sched_getaffinity(0))
            for pid, mask in calls:
                assert pid == 0  # calling thread, per Linux semantics
                assert len(mask) == 1 and mask <= set(avail)
            # loop i → cpu i mod count ⇒ two shards pin DIFFERENT cpus
            # when more than one cpu is available
            if len(avail) > 1:
                assert calls[0][1] != calls[1][1]
        finally:
            fe.stop()

    def test_pinning_inert_when_unsupported(self, monkeypatch):
        """The knob is best-effort by contract: a platform that
        refuses affinity calls must not break serving."""
        import os

        def refuse(pid, mask):
            raise OSError("not permitted")

        monkeypatch.setattr(os, "sched_setaffinity", refuse)
        reg = ModelRegistry()
        reg.deploy("clf", make_mlp(), input_spec=SPEC16,
                   max_batch_size=8, batch_timeout_ms=2.0)
        fe = FrontendServer(reg, port=0, core="eventloop",
                            pin_cpus=True)
        fe.start()
        try:
            x = np.random.default_rng(4).normal(
                0, 1, (2, 16)).astype(np.float32)
            status, _h, body = post(
                fe.port, "/v1/models/clf/predict",
                json.dumps({"inputs": x.tolist()}).encode())
            assert status == 200, body
        finally:
            fe.stop()
            reg.stop_all()

    def test_default_is_unpinned(self, monkeypatch):
        import os
        if not hasattr(os, "sched_setaffinity"):
            pytest.skip("no affinity API")
        calls = []
        monkeypatch.setattr(
            os, "sched_setaffinity",
            lambda pid, mask: calls.append((pid, set(mask))))
        reg = ModelRegistry()
        fe = FrontendServer(reg, port=0, core="eventloop")
        fe.start()
        try:
            time.sleep(0.05)
            assert calls == []  # pin_cpus defaults off
        finally:
            fe.stop()


# ===========================================================================
# registry deploy(service=) contract
# ===========================================================================
class TestDeployService:
    def test_mutually_exclusive_with_model_kwargs(self, lm):
        reg = ModelRegistry()
        dec = DecodeService(lm, slots=1, max_seq_len=16,
                            max_prompt_len=4, prefill_buckets="top",
                            start=False)
        try:
            with pytest.raises(ValueError):
                reg.deploy("x", lm, service=dec)
            with pytest.raises(ValueError):
                reg.deploy("x", service=dec, max_batch_size=4)
            reg.deploy("x", service=dec)
            assert reg.get("x", reg.latest_version("x")) is dec
        finally:
            reg.stop_all()

    def test_undeploy_stops_prebuilt_service(self, lm):
        reg = ModelRegistry()
        dec = DecodeService(lm, slots=1, max_seq_len=16,
                            max_prompt_len=4, prefill_buckets="top")
        reg.deploy("y", service=dec)
        reg.undeploy("y", drain=True)
        assert not dec.alive
        with pytest.raises(ServiceClosed):
            dec.submit([1, 2])


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
