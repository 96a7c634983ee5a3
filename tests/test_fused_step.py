"""Fused K-step driver loop + device-prefetch tests (round-6 perf PR).

Covers the ISSUE-3 acceptance surface:
- fused-vs-unfused equivalence: K∈{1,4} produce the SAME per-iteration
  loss sequence (LeNet-synthetic, CPU) and the same final params;
- trigger/epoch-boundary exactness under partial final blocks:
  validation/checkpoint iteration numbers and shuffle cadence are
  K-invariant;
- device-prefetch determinism across two epochs (MT assembler + device
  block stager in the loop);
- the dispatch-overhead smoke: N iterations at K cost ≤ ceil(N/K)+O(1)
  jit dispatches, counted via a dispatch-counting wrapper.
"""

import math
import os

import jax
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import (DataSet, MTSampleToMiniBatch,
                               SampleToMiniBatch)
from bigdl_tpu.dataset import image, mnist
from bigdl_tpu.engine import Engine
from bigdl_tpu.models.lenet import lenet5
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.optim.trigger import Trigger, probe_fire_step


def mnist_pipeline(n, batch, seed=0, mt=False):
    imgs, labels = mnist.synthetic_mnist(n, seed=seed)
    samples = mnist.to_samples(imgs, labels)
    ds = (DataSet.array(samples)
          >> image.BytesToGreyImg()
          >> image.GreyImgNormalizer(mnist.TRAIN_MEAN, mnist.TRAIN_STD))
    if mt:
        return ds >> MTSampleToMiniBatch(batch, None, workers=2, prefetch=2)
    return ds >> SampleToMiniBatch(batch)


def small_mlp():
    return (nn.Sequential()
            .add(nn.Reshape((784,)))
            .add(nn.Linear(784, 32)).add(nn.ReLU())
            .add(nn.Linear(32, 10)).add(nn.LogSoftMax()))


class RecordingSummary:
    """TrainSummary stand-in: captures the per-iteration replay."""

    def __init__(self):
        self.rows = []  # (step, loss, lr)

    def add_train_step(self, step, loss, lr, throughput):
        self.rows.append((step, loss, lr))

    def add_scalar(self, tag, value, step):
        pass

    def trigger_for(self, name):
        return None

    @property
    def steps(self):
        return [s for s, _, _ in self.rows]

    @property
    def losses(self):
        return np.array([l for _, l, _ in self.rows])


class FiringSpy(Trigger):
    """Wraps a trigger; records the REAL iterations it fired at (probe
    simulations carry state["probe"] and are excluded)."""

    def __init__(self, inner):
        self.inner = inner
        self.fired_at = []

    def __call__(self, state):
        r = self.inner(state)
        if r and not state.get("probe"):
            self.fired_at.append(state["neval"])
        return r


def run_local(k, n=320, batch=32, iters=23, model_fn=small_mlp, mt=False,
              seed=0, **extra):
    rec = RecordingSummary()
    opt = (LocalOptimizer(model_fn(), mnist_pipeline(n, batch, seed=seed,
                                                     mt=mt),
                          nn.ClassNLLCriterion())
           .set_optim_method(optim.Adam(1e-3))
           .set_train_summary(rec)
           .set_end_when(optim.max_iteration(iters)))
    if k is not None:
        opt.set_steps_per_dispatch(k)
    for name, val in extra.items():
        setattr(opt, name, val)
    opt.optimize()
    return rec, opt


class TestFusedEquivalence:
    def test_lenet_synthetic_k4_matches_k1_loss_sequence(self):
        """The ISSUE acceptance bar: identical loss trajectory for
        K∈{1,4} on LeNet-synthetic (CPU), crossing an epoch boundary
        (64 samples / batch 16 = 4 steps per epoch) so partial-block
        flush is in play."""
        seqs = {}
        for k in (1, 4):
            rec, _ = run_local(k, n=64, batch=16, iters=9,
                               model_fn=lenet5)
            seqs[k] = rec
        assert seqs[1].steps == seqs[4].steps == list(range(1, 10))
        np.testing.assert_allclose(seqs[1].losses, seqs[4].losses,
                                   rtol=1e-5, atol=1e-7)

    def test_mlp_k4_matches_k1_params_and_lrs(self):
        r1, o1 = run_local(1)
        r4, o4 = run_local(4)
        assert r1.steps == r4.steps
        np.testing.assert_allclose(r1.losses, r4.losses,
                                   rtol=1e-5, atol=1e-7)
        assert [lr for _, _, lr in r1.rows] == [lr for _, _, lr in r4.rows]
        for a, b in zip(jax.tree_util.tree_leaves(o1.model._params),
                        jax.tree_util.tree_leaves(o4.model._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_oversized_k_flushes_to_epoch_and_end(self):
        # K far larger than the epoch (10 steps) AND the run: blocks
        # flush at every epoch boundary and at max_iteration exactly
        r, o = run_local(64)
        assert r.steps == list(range(1, 24))
        assert o.state["neval"] == 23
        # 10-step epochs: ceil-ish block structure 10|10|3
        assert o._dispatch_count == 3


class TestTriggerEpochExactness:
    def _run(self, k, tmp_path):
        val = mnist_pipeline(64, 32, seed=1)
        vspy = FiringSpy(optim.several_iteration(3))
        cspy = FiringSpy(optim.several_iteration(4))
        shuffles = {"n": 0}
        train = mnist_pipeline(320, 32)
        orig_shuffle = train.shuffle

        def counting_shuffle():
            shuffles["n"] += 1
            orig_shuffle()

        train.shuffle = counting_shuffle
        opt = (LocalOptimizer(small_mlp(), train, nn.ClassNLLCriterion())
               .set_optim_method(optim.Adam(1e-3))
               .set_steps_per_dispatch(k)
               .set_end_when(optim.max_iteration(23))
               .set_validation(vspy, val, [optim.Top1Accuracy()])
               .set_checkpoint(str(tmp_path / f"ck{k}"), cspy))
        opt.optimize()
        ckpts = sorted(os.listdir(str(tmp_path / f"ck{k}")))
        return vspy.fired_at, cspy.fired_at, shuffles["n"], ckpts, opt

    def test_fire_iterations_shuffles_and_checkpoints_k_invariant(
            self, tmp_path):
        """10-step epochs with K=4 force partial blocks (4|4|2) — the
        validation (every 3) and checkpoint (every 4) iterations, the
        shuffle cadence, and the checkpoint FILES must match K=1
        exactly."""
        v1, c1, s1, f1, o1 = self._run(1, tmp_path)
        v4, c4, s4, f4, o4 = self._run(4, tmp_path)
        assert v1 == [3, 6, 9, 12, 15, 18, 21]
        assert (v1, c1, s1) == (v4, c4, s4)
        assert f1 == f4  # same model.<neval> checkpoint set
        assert o1.state["epoch"] == o4.state["epoch"] == 2
        assert o1.state["records_processed_this_epoch"] \
            == o4.state["records_processed_this_epoch"] == 96

    def test_every_epoch_validation_fires_at_epoch_boundaries(self):
        val = mnist_pipeline(64, 32, seed=1)
        fired = {}
        for k in (1, 4):
            spy = FiringSpy(optim.every_epoch())
            opt = (LocalOptimizer(small_mlp(), mnist_pipeline(320, 32),
                                  nn.ClassNLLCriterion())
                   .set_optim_method(optim.Adam(1e-3))
                   .set_steps_per_dispatch(k)
                   .set_end_when(optim.max_epoch(2))
                   .set_validation(spy, val, [optim.Top1Accuracy()]))
            opt.optimize()
            fired[k] = spy.fired_at
        assert fired[1] == fired[4] == [10, 20]

    def test_probe_fire_step_caps_at_trigger_and_epoch(self):
        state = {"neval": 4, "epoch": 0,
                 "records_processed_this_epoch": 128}
        # several_iteration(6) fires at neval 6 → offset 2 from neval 4
        assert probe_fire_step(state, 8, 32, 99999,
                               [optim.several_iteration(6)]) == 2
        # epoch of 320 records ends after 6 more 32-record steps
        assert probe_fire_step(state, 8, 32, 320, []) == 6
        # unknown batch size (0): epoch invisible to the probe
        assert probe_fire_step(state, 8, 0, 320, []) is None
        # probed states are marked, and fire on the simulated epoch flag
        seen = []

        class Probe(Trigger):
            def __call__(self, s):
                seen.append(s.get("probe"))
                return False

        assert probe_fire_step(state, 2, 32, 99999, [Probe()]) is None
        assert seen == [True, True]

    def test_parameters_histogram_trigger_sees_exact_step_params(self,
                                                                 devices):
        """The Parameters summary trigger is probed like any other:
        its firing iteration must end a block, so the logged histogram
        holds THAT iteration's params, not end-of-block ones."""
        hist = {}
        for k in (1, 4):
            rec = RecordingSummary()
            captured = []
            rec.add_histogram = lambda tag, values, step, _c=captured: \
                _c.append((tag, np.array(values, copy=True), step))
            rec.trigger_for = lambda name: (
                optim.several_iteration(3) if name == "Parameters"
                else None)
            opt = (optim.DistriOptimizer(small_mlp(),
                                         mnist_pipeline(320, 32),
                                         nn.ClassNLLCriterion())
                   .set_optim_method(optim.SGD(learning_rate=0.05))
                   .set_steps_per_dispatch(k)
                   .set_seed(5)
                   .set_train_summary(rec)
                   .set_end_when(optim.max_iteration(8)))
            opt.optimize()
            hist[k] = captured
        assert [s for _, _, s in hist[1]] == [s for _, _, s in hist[4]] \
            == [3, 3, 3, 3, 6, 6, 6, 6]  # 4 param leaves × iters 3, 6
        for (t1, v1, s1), (t4, v4, s4) in zip(hist[1], hist[4]):
            assert t1 == t4
            np.testing.assert_allclose(v1, v4, rtol=1e-5, atol=1e-7)

    def test_mid_epoch_resume_fast_forward_k4(self):
        train = mnist_pipeline(256, 32)
        opt = (LocalOptimizer(small_mlp(), train, nn.ClassNLLCriterion())
               .set_optim_method(optim.Adam(1e-3))
               .set_steps_per_dispatch(4)
               .set_state({"records_processed_this_epoch": 128})
               .set_end_when(optim.max_iteration(4)))
        opt.optimize()
        # 128 skipped + 4*32 trained = 256 → exactly one epoch rollover
        assert opt.state["epoch"] == 1
        assert opt.state["records_processed_this_epoch"] == 0


class TestDevicePrefetchDeterminism:
    def test_two_epochs_reproducible_through_prefetch_stages(self):
        """Full pipeline (MT host assembler → device block stager) run
        twice over two epochs: identical loss sequence and identical
        final params — prefetch must not reorder or drop batches."""
        runs = []
        for _ in range(2):
            rec, opt = run_local(4, n=256, batch=32, iters=16, mt=True)
            runs.append((rec, opt))
        (ra, oa), (rb, ob) = runs
        assert ra.steps == rb.steps == list(range(1, 17))
        np.testing.assert_array_equal(ra.losses, rb.losses)
        for a, b in zip(jax.tree_util.tree_leaves(oa.model._params),
                        jax.tree_util.tree_leaves(ob.model._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_prefetch_path_matches_plain_pipeline(self):
        rec_mt, _ = run_local(4, n=256, batch=32, iters=16, mt=True)
        rec_pl, _ = run_local(4, n=256, batch=32, iters=16, mt=False)
        np.testing.assert_allclose(rec_mt.losses, rec_pl.losses,
                                   rtol=1e-6, atol=1e-7)


class _FreshCopies:
    """The input path as it was before buffers were lent: every batch
    in fresh arrays of its own, with no way back to the assembler."""

    def __call__(self, batches):
        from bigdl_tpu.dataset.sample import MiniBatch
        for b in batches:
            yield MiniBatch(np.array(b.input), np.array(b.target))


class TestRecycledBuffersTrainTheSame:
    @pytest.mark.parametrize("k", [1, 4])
    def test_recycling_run_matches_the_copy_path_bitwise(self, k,
                                                         monkeypatch):
        """24 steps (an epoch of 20 and the start of the next) through
        the threaded assembler with buffers really coming back (a
        placer that copies, so the CPU backend cannot alias them, and is
        believed to be a device) against the same steps fed fresh
        arrays all the way: losses and parameters equal to the last
        bit."""
        import jax.numpy as jnp
        from bigdl_tpu.dataset import prefetch
        tmap = jax.tree_util.tree_map

        def run(recycle):
            data = mnist_pipeline(320, 16, mt=True)
            if not recycle:
                data = data >> _FreshCopies()
            rec = RecordingSummary()
            opt = (LocalOptimizer(small_mlp(), data,
                                  nn.ClassNLLCriterion())
                   .set_optim_method(optim.SGD(0.05, momentum=0.9))
                   .set_steps_per_dispatch(k)
                   .set_train_summary(rec)
                   .set_telemetry(True)
                   .set_end_when(optim.max_iteration(24)))
            opt.optimize()
            counters = opt._telemetry.registry.snapshot()["counters"]
            return rec, opt, counters

        ref, ref_opt, ref_counters = run(recycle=False)
        assert ref_counters["input/buffers_recycled"] == 0
        monkeypatch.setattr(
            LocalOptimizer, "_place_train_block",
            lambda self, xs, ys: (tmap(lambda a: jnp.asarray(np.array(a)),
                                       xs),
                                  tmap(lambda a: jnp.asarray(np.array(a)),
                                       ys)))
        monkeypatch.setattr(prefetch, "_may_alias_host",
                            lambda placed: False)
        got, got_opt, counters = run(recycle=True)
        assert counters["input/buffers_recycled"] > 0
        assert counters["input/buffers_recycled"] \
            + counters["input/buffers_allocated"] == 24
        assert got.steps == ref.steps == list(range(1, 25))
        np.testing.assert_array_equal(got.losses, ref.losses)
        for a, b in zip(jax.tree_util.tree_leaves(got_opt.model._params),
                        jax.tree_util.tree_leaves(ref_opt.model._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDispatchBudget:
    def test_fused_loop_dispatch_count_smoke(self, monkeypatch):
        """N iterations at steps_per_dispatch=K must issue
        ≤ ceil(N/K)+O(1) jit dispatches — counted via a wrapper around
        the built block fn, so the budget holds for the ACTUAL compiled
        callables, not a driver-side counter."""
        calls = {"n": 0}
        orig = LocalOptimizer._build_block_fn

        def counting_build(self, grad_fn, k):
            fn = orig(self, grad_fn, k)

            def wrapped(*a, **kw):
                calls["n"] += 1
                return fn(*a, **kw)

            return wrapped

        monkeypatch.setattr(LocalOptimizer, "_build_block_fn",
                            counting_build)
        N, K = 24, 4
        rec, opt = run_local(K, n=2048, batch=16, iters=N)
        assert rec.steps == list(range(1, N + 1))
        budget = math.ceil(N / K) + 2
        assert calls["n"] <= budget, (calls["n"], budget)
        assert opt._dispatch_count == calls["n"]

    def test_k1_still_one_dispatch_per_iteration(self):
        rec, opt = run_local(1, n=2048, batch=16, iters=8)
        assert opt._dispatch_count == 8


class TestDistriFused:
    def test_spmd_k4_matches_k1_with_zero1(self, devices):
        """The fused block through the SPMD path: batches sharded
        P(None, "data"), ZeRO-1 sharded optimizer update constrained
        inside the scanned step — must reproduce the K=1 trajectory."""
        recs = {}
        for k in (1, 4):
            rec = RecordingSummary()
            opt = (optim.DistriOptimizer(small_mlp(),
                                         mnist_pipeline(320, 32),
                                         nn.ClassNLLCriterion(),
                                         parameter_sharding=True)
                   .set_optim_method(optim.SGD(learning_rate=0.05,
                                               momentum=0.9))
                   .set_steps_per_dispatch(k)
                   .set_seed(5)
                   .set_train_summary(rec)
                   .set_end_when(optim.max_iteration(12)))
            opt.optimize()
            recs[k] = (rec, opt)
        (r1, o1), (r4, o4) = recs[1], recs[4]
        assert r1.steps == r4.steps == list(range(1, 13))
        np.testing.assert_allclose(r1.losses, r4.losses,
                                   rtol=1e-5, atol=1e-7)
        assert o4._dispatch_count < o1._dispatch_count
        for a, b in zip(jax.tree_util.tree_leaves(o1.model._params),
                        jax.tree_util.tree_leaves(o4.model._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestConfigSurface:
    def test_engine_default_flows_into_driver(self):
        prev = Engine._state.steps_per_dispatch
        try:
            Engine.set_steps_per_dispatch(4)
            rec, opt = run_local(None, n=2048, batch=16, iters=8)
            assert opt._dispatch_count == 2  # 8 iters / K=4
        finally:
            Engine._state.steps_per_dispatch = prev

    def test_invalid_k_rejected(self):
        with pytest.raises(ValueError):
            Engine.set_steps_per_dispatch(0)
        with pytest.raises(ValueError):
            LocalOptimizer(small_mlp(), mnist_pipeline(64, 32),
                           nn.ClassNLLCriterion()).set_steps_per_dispatch(0)

    def test_config_env_field_exists(self):
        from bigdl_tpu.utils.config import Config
        assert Config().steps_per_dispatch == 1


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
