"""grad_sync (explicit bucketed gradient synchronization) tests — the
ISSUE-4 acceptance surface, on the virtual 8-device CPU mesh:

- f32 wire is BITWISE-equal to a plain f32 psum step (reduce-scatter +
  owned-slice update + all-gather ≡ all-reduce + full update);
- bf16 wire tracks the f32 loss trajectory within tolerance and still
  learns;
- ZeRO-1 slice-update equality: grad_sync-trained params match the
  replicated-update baseline, and the per-chip f32 master slices
  reassemble exactly into the published params (f32 wire);
- K ∈ {1, 4} dispatch fusion is invariant through grad_sync;
- bucket planning round-trips arbitrary pytrees and caps bucket sizes;
- the shared stochastic_round hoist (utils/precision.py) keeps the
  optim_method back-compat alias and its unbiasedness;
- config/engine surface: grad_bucket_bytes / grad_wire_dtype fields,
  Engine.set_xla_async_collectives flag plumbing.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
from bigdl_tpu.dataset import image, mnist
from bigdl_tpu.parallel import grad_sync as gs


def mnist_pipeline(n, batch, seed=0):
    imgs, labels = mnist.synthetic_mnist(n, seed=seed)
    samples = mnist.to_samples(imgs, labels)
    return (DataSet.array(samples)
            >> image.BytesToGreyImg()
            >> image.GreyImgNormalizer(mnist.TRAIN_MEAN, mnist.TRAIN_STD)
            >> SampleToMiniBatch(batch))


def small_mlp():
    return (nn.Sequential()
            .add(nn.Reshape((784,)))
            .add(nn.Linear(784, 64)).add(nn.ReLU())
            .add(nn.Linear(64, 10)).add(nn.LogSoftMax()))


class RecordingSummary:
    def __init__(self):
        self.losses = []

    def add_train_step(self, step, loss, lr, throughput):
        self.losses.append(loss)

    def add_scalar(self, *a):
        pass

    def trigger_for(self, name):
        return None


def train_distri(seed=5, iters=6, k=None, lr=0.05, momentum=0.9,
                 summary=None, **kw):
    model = small_mlp()
    opt = (optim.DistriOptimizer(model, mnist_pipeline(512, 64),
                                 nn.ClassNLLCriterion(), **kw)
           .set_optim_method(optim.SGD(learning_rate=lr,
                                       momentum=momentum))
           .set_seed(seed)
           .set_end_when(optim.max_iteration(iters)))
    if k is not None:
        opt.set_steps_per_dispatch(k)
    if summary is not None:
        opt.set_train_summary(summary)
    opt.optimize()
    return model, opt


class TestBucketPlan:
    def tree(self):
        r = np.random.default_rng(0)
        return {
            "a": jnp.asarray(r.normal(0, 1, (7, 5)).astype(np.float32)),
            "b": [jnp.asarray(r.normal(0, 1, (33,)).astype(np.float32)),
                  jnp.asarray(r.normal(0, 1, (4, 4, 2))
                              .astype(np.float32))],
            "c": jnp.asarray(r.normal(0, 1, (3,)).astype(np.float32)),
        }

    def test_round_trip(self):
        t = self.tree()
        plan = gs.build_plan(t, n_shard=8, bucket_bytes=1 << 20)
        buckets = gs.flatten_to_buckets(plan, t)
        back = gs.unflatten_from_buckets(plan, buckets)
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype

    def test_padding_divides_shards(self):
        t = self.tree()
        for n in (2, 4, 8):
            plan = gs.build_plan(t, n_shard=n, bucket_bytes=1 << 20)
            for sz in plan.bucket_sizes:
                assert sz % n == 0 and sz >= n

    def test_size_cap_splits_buckets(self):
        t = self.tree()  # leaf sizes 35, 33, 32, 3
        # 40 f32 elements per bucket: leaves may not merge beyond cap,
        # but an oversized leaf still gets (its own) bucket
        plan = gs.build_plan(t, n_shard=2, bucket_bytes=40 * 4)
        assert plan.num_buckets >= 3
        covered = sorted(i for b in plan.buckets for i in b)
        assert covered == [0, 1, 2, 3]
        # and a huge cap packs everything into one bucket
        plan1 = gs.build_plan(t, n_shard=2, bucket_bytes=1 << 30)
        assert plan1.num_buckets == 1
        # degenerate caps floor at one ELEMENT (not zero): every leaf
        # gets its own bucket, and the round-trip still holds
        plan0 = gs.build_plan(t, n_shard=2, bucket_bytes=1)
        assert plan0.num_buckets == 4
        back = gs.unflatten_from_buckets(
            plan0, gs.flatten_to_buckets(plan0, t))
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wire_dtype_resolution(self):
        assert gs.resolve_wire_dtype("f32") is jnp.float32
        assert gs.resolve_wire_dtype("bfloat16") is jnp.bfloat16
        assert gs.resolve_wire_dtype("f16") is jnp.float16
        with pytest.raises(ValueError, match="wire dtype"):
            gs.resolve_wire_dtype("int8")


class TestStochasticRoundHoist:
    def test_backcompat_alias(self):
        from bigdl_tpu.optim import optim_method
        from bigdl_tpu.utils import precision
        assert optim_method._stochastic_round is precision.stochastic_round

    def test_unbiased_in_expectation(self):
        from bigdl_tpu.utils.precision import stochastic_round
        x = jnp.full((512,), 1.0 + 2 ** -12, jnp.float32)  # between ulps
        acc = np.zeros((512,), np.float64)
        n = 64
        for i in range(n):
            r = stochastic_round(x, jnp.bfloat16,
                                 jax.random.PRNGKey(i))
            acc += np.asarray(r, np.float64)
        mean = acc.mean() / n
        assert abs(mean - float(x[0])) < 2e-4, mean
        # plain round-to-nearest would pin every element to 1.0 exactly
        assert mean != 1.0

    def test_identity_paths(self):
        from bigdl_tpu.utils.precision import stochastic_round
        x = jnp.ones((4,), jnp.float32)
        assert stochastic_round(x, jnp.float32,
                                jax.random.PRNGKey(0)) is x
        y = stochastic_round(x, jnp.float16, jax.random.PRNGKey(0))
        assert y.dtype == jnp.float16

    def test_f16_wire_saturates_instead_of_inf(self):
        # a gradient spike must clamp on the wire — an inf would psum
        # into the masters and train NaNs silently
        x = jnp.asarray([1e6, -1e6, 1.0], jnp.float32)
        w = gs.wire_cast(x, jnp.float16, jax.random.PRNGKey(0))
        assert w.dtype == jnp.float16
        assert np.all(np.isfinite(np.asarray(w, np.float32)))
        assert float(w[0]) == float(jnp.finfo(jnp.float16).max)
        assert float(w[2]) == 1.0

    def test_f16_wire_clamp_budgets_the_psum(self):
        # the clamp must bound the n-chip SUM, not just each chip's
        # value: n chips each at 6e4 (individually within f16 range)
        # would overflow the f16 accumulation without the /n budget
        n = 8
        x = jnp.full((4,), 6e4, jnp.float32)
        w = gs.wire_cast(x, jnp.float16, jax.random.PRNGKey(0), n_sum=n)
        lim = float(jnp.finfo(jnp.float16).max) / n
        assert float(np.max(np.asarray(w, np.float32))) <= lim
        total = np.float16(0)
        for _ in range(n):  # worst-case coherent f16 accumulation
            total = np.float16(total + np.asarray(w, np.float16)[0])
        assert np.isfinite(total)


class TestGradSyncNumerics:
    """The core acceptance gates: explicit reduce-scatter/update/gather
    vs plain psum, driven through the exact shard_map machinery."""

    def _setup(self, devices):
        mesh = Mesh(np.array(devices), ("data",))
        model = small_mlp()
        params, mstate = model.init(jax.random.PRNGKey(0))
        crit = nn.ClassNLLCriterion()
        method = optim.SGD(learning_rate=0.05, momentum=0.9)
        r = np.random.default_rng(0)
        xs = jnp.asarray(r.normal(0, 1, (6, 64, 1, 28, 28))
                         .astype(np.float32))
        ys = jnp.asarray(r.integers(0, 10, (6, 64)).astype(np.int32))

        def loss_fn(p, ms, x, y):
            out, ms2 = model.apply(p, ms, x, training=True)
            return crit.apply(out, y), ms2

        return mesh, params, mstate, method, \
            jax.value_and_grad(loss_fn, has_aux=True), xs, ys

    def test_f32_wire_bitwise_vs_psum(self, devices):
        mesh, params, mstate, method, grad_fn, xs, ys = \
            self._setup(devices)
        n = 8
        plan = gs.build_plan(params, n, 1 << 14)  # force several buckets
        assert plan.num_buckets > 1
        gstate = gs.init_state(plan, params, method)
        repl = jax.tree_util.tree_map(lambda _: P(), params)
        replm = jax.tree_util.tree_map(lambda _: P(), mstate)
        gspec = jax.tree_util.tree_map(lambda _: P("data"), gstate)

        def gs_step(p, ms, st, x, y, it):
            (loss, ms2), g = grad_fn(p, ms, x, y)
            p2, st2 = gs.sync_and_update(plan, g, st, method, 0.05, it,
                                         wire_dtype=jnp.float32,
                                         axis_name="data")
            return p2, ms2, st2, lax.pmean(loss, "data")

        ostate = method.init_state(params)
        ospec = jax.tree_util.tree_map(lambda _: P(), ostate)

        def psum_step(p, ms, os_, x, y, it):
            (loss, ms2), g = grad_fn(p, ms, x, y)
            g = jax.tree_util.tree_map(
                lambda a: lax.psum(a / n, "data"), g)
            p2, os2 = method.update(g, p, os_, 0.05, it)
            return p2, ms2, os2, lax.pmean(loss, "data")

        f_gs = jax.jit(gs.shard_map_unchecked(
            gs_step, mesh, (repl, replm, gspec, P("data"), P("data"),
                            P()), (repl, replm, gspec, P())))
        f_ps = jax.jit(gs.shard_map_unchecked(
            psum_step, mesh, (repl, replm, ospec, P("data"), P("data"),
                              P()), (repl, replm, ospec, P())))

        pa, pb = params, params
        sa, sb = gstate, ostate
        ma = mb = mstate
        for t in range(xs.shape[0]):
            pa, ma, sa, la = f_gs(pa, ma, sa, xs[t], ys[t], t)
            pb, mb, sb, lb = f_ps(pb, mb, sb, xs[t], ys[t], t)
            assert np.asarray(la) == np.asarray(lb), t
            for a, b in zip(jax.tree_util.tree_leaves(pa),
                            jax.tree_util.tree_leaves(pb)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
        # master slices reassemble bitwise into the published params
        masters = [np.asarray(m) for m in sa["master"]]
        flat_params = [np.asarray(b) for b in
                       gs.flatten_to_buckets(plan, pa)]
        for m, fp in zip(masters, flat_params):
            np.testing.assert_array_equal(m, fp)

    def test_bf16_wire_tracks_f32_within_tol(self, devices):
        rec32, rec16 = RecordingSummary(), RecordingSummary()
        train_distri(iters=8, summary=rec32, grad_wire_dtype="f32")
        m16, o16 = train_distri(iters=8, summary=rec16,
                                grad_wire_dtype="bf16")
        l32, l16 = np.array(rec32.losses), np.array(rec16.losses)
        assert l32.shape == l16.shape == (8,)
        np.testing.assert_allclose(l16, l32, rtol=0.05, atol=0.02)
        assert np.all(np.isfinite(l16))
        # masters stay exact f32 even under the compressed wire
        for m in o16._final_opt_state["master"]:
            assert m.dtype == jnp.float32


class TestGradSyncDriver:
    def test_enabled_by_default_for_pure_dp(self, devices):
        _, opt = train_distri(iters=2)
        assert opt._use_grad_sync
        assert opt._gs_plan is not None

    def test_zero1_slice_update_equality_vs_replicated(self, devices):
        m1, o1 = train_distri(iters=4, seed=5)  # grad_sync ZeRO-1
        m2, o2 = train_distri(iters=4, seed=5,
                              parameter_sharding=False)  # replicated
        assert o1._use_grad_sync and not o2._use_grad_sync
        for a, b in zip(jax.tree_util.tree_leaves(m1._params),
                        jax.tree_util.tree_leaves(m2._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_k_invariance_through_grad_sync(self, devices):
        recs = {}
        for k in (1, 4):
            rec = RecordingSummary()
            _, opt = train_distri(iters=8, k=k, summary=rec)
            assert opt._use_grad_sync
            recs[k] = (np.array(rec.losses), opt)
        l1, o1 = recs[1]
        l4, o4 = recs[4]
        np.testing.assert_allclose(l1, l4, rtol=1e-5, atol=1e-7)
        assert o4._dispatch_count < o1._dispatch_count
        for a, b in zip(jax.tree_util.tree_leaves(o1.model._params),
                        jax.tree_util.tree_leaves(o4.model._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("clip", ["l2", "value"])
    def test_clip_matches_replicated_baseline(self, clip, devices):
        """Both clip kinds, applied to owned slices of the REDUCED
        gradient, must reproduce the replicated-baseline clipping
        (value = elementwise; l2 = psum of per-slice square sums)."""
        def run(**kw):
            model = small_mlp()
            opt = (optim.DistriOptimizer(model, mnist_pipeline(512, 64),
                                         nn.ClassNLLCriterion(), **kw)
                   .set_optim_method(optim.SGD(learning_rate=0.5))
                   .set_seed(5)
                   .set_end_when(optim.max_iteration(4)))
            if clip == "l2":
                opt.set_gradient_clipping_by_l2_norm(0.5)
            else:
                opt.set_gradient_clipping_by_value(-3e-3, 3e-3)
            opt.optimize()
            return model, opt

        m1, o1 = run()
        m2, _ = run(parameter_sharding=False)
        assert o1._use_grad_sync
        for a, b in zip(jax.tree_util.tree_leaves(m1._params),
                        jax.tree_util.tree_leaves(m2._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_lbfgs_rejected_with_clear_error(self, devices):
        model = small_mlp()
        opt = (optim.DistriOptimizer(model, mnist_pipeline(64, 32),
                                     nn.ClassNLLCriterion())
               .set_optim_method(optim.LBFGS())
               .set_end_when(optim.max_iteration(1)))
        with pytest.raises(ValueError, match="elementwise"):
            opt.optimize()

    def test_explicit_grad_sync_on_tp_mesh_rejected(self, devices):
        from bigdl_tpu.parallel import create_mesh
        mesh = create_mesh(data=2, model=4)
        with pytest.raises(ValueError, match="pure data-parallel"):
            (optim.DistriOptimizer(small_mlp(), mnist_pipeline(64, 32),
                                   nn.ClassNLLCriterion(), mesh=mesh,
                                   grad_sync=True)
             .set_end_when(optim.max_iteration(1))
             .optimize())

    def test_stale_non_gs_checkpoint_rejected_loudly(self, devices):
        """A retry/resume checkpoint written by the pre-grad_sync path
        must fail with a format message, not an opaque trace-time
        KeyError."""
        opt = (optim.DistriOptimizer(small_mlp(), mnist_pipeline(64, 32),
                                     nn.ClassNLLCriterion())
               .set_optim_method(optim.SGD(learning_rate=0.05,
                                           momentum=0.9))
               .set_end_when(optim.max_iteration(1)))
        opt._resume_opt_state = {"velocity": {"0": np.zeros((4,),
                                                           np.float32)}}
        with pytest.raises(ValueError, match="not grad_sync-format"):
            opt.optimize()

    def test_checkpoint_resume_roundtrips_gs_state(self, tmp_path,
                                                   devices):
        from bigdl_tpu.utils import checkpoint as ckpt
        path = str(tmp_path / "ck")
        model = small_mlp()
        opt = (optim.DistriOptimizer(model, mnist_pipeline(256, 32),
                                     nn.ClassNLLCriterion())
               .set_optim_method(optim.SGD(learning_rate=0.05,
                                           momentum=0.9))
               .set_seed(5)
               .set_end_when(optim.max_iteration(4))
               .set_checkpoint(path, optim.several_iteration(2)))
        opt.optimize()
        blob = ckpt.load_checkpoint(ckpt.latest_checkpoint(path))
        st = blob["opt_state"]
        assert set(st) == {"master", "opt"}
        assert isinstance(st["master"], list)
        # masters in the checkpoint equal the final published params
        plan = opt._gs_plan
        for m, fp in zip(st["master"],
                         gs.flatten_to_buckets(plan, model._params)):
            np.testing.assert_allclose(np.asarray(m), np.asarray(fp),
                                       rtol=0, atol=0)


class TestConfigEngineSurface:
    def test_config_fields(self):
        from bigdl_tpu.utils.config import Config
        c = Config()
        assert c.grad_bucket_bytes == 4 << 20
        assert c.grad_wire_dtype == "f32"

    def test_env_overlay(self, monkeypatch):
        from bigdl_tpu.utils.config import Config
        monkeypatch.setenv("BIGDL_TPU_GRAD_WIRE_DTYPE", "bf16")
        monkeypatch.setenv("BIGDL_TPU_GRAD_BUCKET_BYTES", "1048576")
        c = Config.from_env()
        assert c.grad_wire_dtype == "bf16"
        assert c.grad_bucket_bytes == 1 << 20

    def test_wire_dtype_constructor_override(self, devices):
        _, opt = train_distri(iters=1, grad_wire_dtype="bf16")
        assert opt._gs_wire is jnp.bfloat16

    def test_set_xla_async_collectives(self, monkeypatch):
        from bigdl_tpu.engine import Engine
        monkeypatch.setenv("XLA_FLAGS", "--foo=1")
        prev = Engine._state.xla_async_collectives
        try:
            # no probe child, no private jax read: the flags are
            # written, and a backend that rejects them fails at start
            Engine.set_xla_async_collectives(True)
            flags = os.environ["XLA_FLAGS"]
            assert "--foo=1" in flags
            assert "--xla_tpu_enable_latency_hiding_scheduler=true" \
                in flags
            assert Engine.xla_async_collectives() is True
            # a re-call with the other value replaces, never duplicates
            Engine.set_xla_async_collectives(False)
            flags = os.environ["XLA_FLAGS"].split()
            assert flags.count("--xla_tpu_enable_latency_hiding_"
                               "scheduler=false") == 1
            assert not any(f.endswith("=true") for f in flags
                           if f.startswith("--xla_tpu_enable_"))
            assert Engine.xla_async_collectives() is False
        finally:
            Engine._state.xla_async_collectives = prev


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
