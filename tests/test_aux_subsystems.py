"""Aux subsystems: LBFGS+LineSearch, per-layer profiling, unified config,
TF control-flow (Switch/Merge) import.

Reference analogs: ``DL/optim/LBFGS.scala``+``LineSearch.scala``,
``AbstractModule.getTimes`` (``AbstractModule.scala:254-287``),
the ``bigdl.*`` property soup (``Engine.scala:45-47``), and the
DynamicGraph ``Scheduler`` (``nn/Scheduler.scala:104-145``).
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfgraph_util import attr_tensor, node, scalar_const, shape_const  # noqa: E501
from bigdl_tpu import nn, optim


class TestLBFGS:
    def test_minimize_rosenbrock(self):
        def rosen(p):
            x, y = p["x"], p["y"]
            return (1 - x) ** 2 + 100.0 * (y - x * x) ** 2

        feval = jax.jit(jax.value_and_grad(rosen))
        p0 = {"x": jnp.asarray(-1.2), "y": jnp.asarray(1.0)}
        p, loss, it = optim.LBFGS(history=10).minimize(feval, p0,
                                                       max_iter=100)
        assert loss < 1e-8
        np.testing.assert_allclose(float(p["x"]), 1.0, atol=1e-3)

    def test_update_contract_under_jit(self):
        A = jnp.asarray(np.diag([1.0, 10.0, 100.0]))

        def q(p):
            return 0.5 * p["w"] @ A @ p["w"]

        lb = optim.LBFGS(history=5)
        params = {"w": jnp.asarray([1.0, 1.0, 1.0])}
        st = lb.init_state(params)
        vg = jax.value_and_grad(q)
        upd = jax.jit(lb.update)
        for i in range(50):
            _, g = vg(params)
            params, st = upd(g, params, st, 0.5, i)
        assert float(q(params)) < 1e-6

    def test_trains_via_optimizer(self):
        # full-batch logistic regression through the normal Optimizer API
        rng = np.random.RandomState(0)
        x = rng.randn(128, 4).astype(np.float32)
        w_true = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
        y = (x @ w_true > 0).astype(np.int32)
        from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
        from bigdl_tpu.dataset.sample import Sample
        samples = [Sample(x[i], y[i]) for i in range(128)]
        model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
        opt = (optim.LocalOptimizer(
                   model, DataSet.array(samples) >> SampleToMiniBatch(128),
                   nn.ClassNLLCriterion())
               .set_optim_method(optim.LBFGS(learning_rate=0.5))
               .set_end_when(optim.max_epoch(30)))
        opt.optimize()
        model.training = False
        acc = (np.argmax(np.asarray(model.forward(x)), -1) == y).mean()
        assert acc > 0.95, acc


class TestProfiling:
    def test_get_times_per_layer(self):
        from bigdl_tpu.utils.profiling import format_times, get_times
        m = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                          nn.Linear(128, 8), nn.LogSoftMax())
        m.initialize()
        x = jnp.ones((16, 64))
        times = get_times(m, x, repeats=2)
        names = [t.name for t in times]
        # one row per leaf (execution order) + total
        assert sum("Linear" in n for n in names) == 2
        assert all(t.forward_s >= 0 for t in times)
        table = format_times(times)
        assert "fwd(ms)" in table and "Linear" in table

    def test_profile_step_writes_trace(self, tmp_path):
        from bigdl_tpu.utils.profiling import profile_step
        f = jax.jit(lambda x: jnp.sum(x * x))
        out = profile_step(f, jnp.ones((128, 128)),
                           log_dir=str(tmp_path), steps=2)
        assert np.isfinite(float(out))
        # a trace directory appeared
        found = any("plugins" in root or f
                    for root, _, f in os.walk(tmp_path))
        assert found


class TestConfig:
    def test_env_overlay_and_configure(self, monkeypatch):
        from bigdl_tpu.utils import config as C
        C.reset_config()
        monkeypatch.setenv("BIGDL_TPU_FAILURE_RETRY_TIMES", "7")
        monkeypatch.setenv("BIGDL_TPU_COMPUTE_DTYPE", "bfloat16")
        cfg = C.get_config()
        assert cfg.failure_retry_times == 7
        assert cfg.compute_dtype == "bfloat16"
        C.configure(loader_workers=12)
        assert C.get_config().loader_workers == 12
        with pytest.raises(AttributeError):
            C.configure(nonsense=1)
        C.reset_config()

    def test_engine_reads_config_default(self):
        from bigdl_tpu.utils import config as C
        C.reset_config()
        from bigdl_tpu.engine import _EngineState
        assert _EngineState().failure_retry_times == \
            C.get_config().failure_retry_times


def _tiny_optimizer(k=None):
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (16,)).astype(np.float32),
                      np.int32(rng.integers(0, 4)))
               for _ in range(64)]
    model = nn.Sequential(nn.Linear(16, 16), nn.ReLU(),
                          nn.Linear(16, 4), nn.LogSoftMax())
    opt = (optim.LocalOptimizer(model,
                                DataSet.array(samples)
                                >> SampleToMiniBatch(8),
                                nn.ClassNLLCriterion())
           .set_optim_method(optim.SGD(learning_rate=0.1))
           .set_end_when(optim.max_iteration(8)))
    if k is not None:
        opt.set_steps_per_dispatch(k)
    return opt


def _grad_sync_of(**ctor):
    """(wire dtype, bucket count) DistriOptimizer's own
    ``_resolve_grad_sync`` settles on for a model of two Linear layers:
    272 + 68 f32 elements, the larger bias 16."""
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    model = nn.Sequential(nn.Linear(16, 16), nn.ReLU(),
                          nn.Linear(16, 4), nn.LogSoftMax()).initialize(0)
    opt = DistriOptimizer(model, None, nn.ClassNLLCriterion(),
                          parameter_sharding=True, **ctor)
    opt._resolve_grad_sync(Engine.get_mesh(), model._params)
    return opt._gs_wire, len(opt._gs_plan.buckets)


def _int8_block_rows_of(monkeypatch, **call):
    """The ``block_rows`` ``int8_matmul`` hands its row plan."""
    from bigdl_tpu.ops import pallas_int8_gemm as g
    seen = []
    real = g._pad_plan

    def spy(N, dtype, block_rows):
        seen.append(block_rows)
        return real(N, dtype, block_rows)

    monkeypatch.setattr(g, "_pad_plan", spy)
    x = jnp.ones((256, 128), jnp.float32)
    wq = jnp.ones((128, 128), jnp.int8)
    g.int8_matmul(x, wq, jnp.ones((128,), jnp.float32), **call)
    assert seen
    return seen[0]


class TestKnobResolution:
    """The one rule (``utils/config.py``): per-object setter or
    constructor argument > ``Engine.set_*`` > ``configure()`` >
    ``BIGDL_TPU_*`` > dataclass default — walked from the bottom up at
    the call site the product itself resolves each knob at."""

    @pytest.fixture(autouse=True)
    def _fresh(self):
        from bigdl_tpu.engine import Engine
        from bigdl_tpu.utils.config import reset_config
        reset_config()
        Engine.reset()
        yield
        reset_config()
        Engine.reset()

    # knob, env string, the value the env gives, a configure() value
    CASES = [
        ("steps_per_dispatch", "5", 5, 7),
        ("grad_wire_dtype", "f16", "f16", "bf16"),
        ("kernel_impl", "pallas", "pallas", "xla"),
        ("activation_memory", "dots", "dots", "full"),
        ("grad_bucket_bytes", "1048576", 1 << 20, 2 << 20),
        ("int8_block_rows", "64", 64, 128),
        ("serving_max_batch_size", "16", 16, 8),
        ("serving_batch_timeout_ms", "1.5", 1.5, 0.5),
        ("serving_queue_capacity", "64", 64, 128),
        ("serving_row_buckets", "top", "top", "8,16,32"),
        ("serving_deadline_ms", "250", 250.0, 100.0),
    ]

    @pytest.mark.parametrize("knob,envs,envv,expl", CASES)
    def test_chain(self, monkeypatch, knob, envs, envv, expl):
        from bigdl_tpu.utils.config import (Config, configure, get_config,
                                            reset_config)
        default = getattr(Config(), knob)
        assert default not in (envv, expl)
        assert getattr(get_config(), knob) == default
        monkeypatch.setenv("BIGDL_TPU_" + knob.upper(), envs)
        reset_config()
        got = getattr(get_config(), knob)
        assert got == envv and type(got) is type(default)
        configure(**{knob: expl})
        assert getattr(get_config(), knob) == expl
        # a reset forgets configure(), not the environment
        reset_config()
        assert getattr(get_config(), knob) == envv

    def test_engine_steps_per_dispatch_chain(self, monkeypatch):
        """All five levels, by the dispatches the driver issues for 8
        iterations (one epoch of 8 batches)."""
        from bigdl_tpu.engine import Engine
        from bigdl_tpu.utils.config import configure, reset_config

        def dispatches(k=None):
            opt = _tiny_optimizer(k)
            opt.optimize()
            return opt._dispatch_count

        assert Engine.steps_per_dispatch() == 1
        assert dispatches() == 8
        monkeypatch.setenv("BIGDL_TPU_STEPS_PER_DISPATCH", "2")
        reset_config()
        assert Engine.steps_per_dispatch() == 2
        assert dispatches() == 4
        configure(steps_per_dispatch=4)
        assert Engine.steps_per_dispatch() == 4
        assert dispatches() == 2
        Engine.set_steps_per_dispatch(8)
        assert Engine.steps_per_dispatch() == 8
        assert dispatches() == 1
        # the optimizer's own setter tops everything
        assert dispatches(k=1) == 8

    def test_engine_kernel_impl_chain(self, monkeypatch):
        from bigdl_tpu.engine import Engine
        from bigdl_tpu.ops import resolve_kernel_impl
        from bigdl_tpu.utils.config import configure, reset_config
        assert Engine.kernel_impl() == "auto"
        assert resolve_kernel_impl() == "xla"  # auto, off the TPU
        monkeypatch.setenv("BIGDL_TPU_KERNEL_IMPL", "pallas")
        reset_config()
        assert Engine.kernel_impl() == "pallas"
        configure(kernel_impl="xla")
        assert Engine.kernel_impl() == "xla"
        Engine.set_kernel_impl("pallas")
        assert resolve_kernel_impl() == "pallas"
        # a layer's own impl= tops everything
        assert resolve_kernel_impl("xla") == "xla"
        monkeypatch.setenv("BIGDL_TPU_KERNEL_IMPL", "mosaic")
        reset_config()
        Engine.reset()
        with pytest.raises(ValueError):
            Engine.kernel_impl()

    def test_activation_memory_explicit_none_beats_env(self, monkeypatch):
        """set_activation_memory(None) is the documented INERT policy,
        not 'unset': it must override a configure()/env value exactly
        like 'none' does (only a never-called setter lets Config fill
        the knob)."""
        from bigdl_tpu.utils.config import configure, reset_config

        def opt():
            model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
            return optim.LocalOptimizer(model, None,
                                        nn.ClassNLLCriterion())

        assert opt()._resolved_activation_memory() == "none"
        monkeypatch.setenv("BIGDL_TPU_ACTIVATION_MEMORY", "full")
        reset_config()
        assert opt()._resolved_activation_memory() == "full"
        configure(activation_memory="dots")
        assert opt()._resolved_activation_memory() == "dots"
        assert opt().set_activation_memory(
            "bf16")._resolved_activation_memory() == "bf16"
        assert opt().set_activation_memory(
            None)._resolved_activation_memory() == "none"
        # garbage from the environment fails where it is resolved
        monkeypatch.setenv("BIGDL_TPU_ACTIVATION_MEMORY", "most")
        reset_config()
        with pytest.raises(ValueError):
            opt()._resolved_activation_memory()

    def test_grad_sync_knobs_chain(self, monkeypatch):
        from bigdl_tpu.utils.config import configure, reset_config
        assert _grad_sync_of() == (jnp.float32, 1)
        monkeypatch.setenv("BIGDL_TPU_GRAD_WIRE_DTYPE", "f16")
        monkeypatch.setenv("BIGDL_TPU_GRAD_BUCKET_BYTES", "64")
        reset_config()
        assert _grad_sync_of() == (jnp.float16, 4)  # a leaf a bucket
        configure(grad_wire_dtype="bf16", grad_bucket_bytes=1100)
        assert _grad_sync_of() == (jnp.bfloat16, 2)  # a layer a bucket
        # constructor arguments top everything
        assert _grad_sync_of(grad_wire_dtype="f32",
                             grad_bucket_bytes=1 << 20) == (jnp.float32, 1)

    def test_serving_defaults_from_the_environment(self, monkeypatch):
        from bigdl_tpu.engine import Engine
        from bigdl_tpu.serving import InferenceService
        from bigdl_tpu.utils.config import configure, reset_config
        d0 = Engine.serving_defaults()
        assert d0["max_batch_size"] == 32 and d0["row_buckets"] == ""
        monkeypatch.setenv("BIGDL_TPU_SERVING_MAX_BATCH_SIZE", "16")
        monkeypatch.setenv("BIGDL_TPU_SERVING_BATCH_TIMEOUT_MS", "1.5")
        monkeypatch.setenv("BIGDL_TPU_SERVING_ROW_BUCKETS", "top")
        reset_config()
        d = Engine.serving_defaults()
        assert d["max_batch_size"] == 16
        assert d["batch_timeout_ms"] == 1.5
        assert d["row_buckets"] == "top"
        configure(serving_batch_timeout_ms=0.5)
        model = nn.Sequential(nn.Linear(16, 4)).initialize(0)
        svc = InferenceService(model, start=False)
        assert (svc.max_batch_size, svc.batch_timeout_ms, svc.buckets) \
            == (16, 0.5, (16,))
        svc.stop()
        # constructor arguments top everything
        svc = InferenceService(model, max_batch_size=8, buckets="pow2",
                               start=False)
        assert (svc.max_batch_size, svc.buckets) == (8, (1, 2, 4, 8))
        svc.stop()

    def test_configured_block_rows_picked_up_by_kernel(self, monkeypatch):
        """int8_matmul's block_rows=None defers to
        ``Config.int8_block_rows``; an explicit argument beats it."""
        from bigdl_tpu.utils.config import configure, reset_config
        assert _int8_block_rows_of(monkeypatch) == 0
        monkeypatch.setenv("BIGDL_TPU_INT8_BLOCK_ROWS", "64")
        reset_config()
        assert _int8_block_rows_of(monkeypatch) == 64
        configure(int8_block_rows=128)
        assert _int8_block_rows_of(monkeypatch) == 128
        assert _int8_block_rows_of(monkeypatch, block_rows=32) == 32


class TestControlFlowImport:
    def _cond_graph(self, tmp_path):
        from bigdl_tpu.utils import protowire as pw



        g = (node("x", "Placeholder")
             + node("pred", "Placeholder")
             + node("sw", "Switch", ["x", "pred"])
             + node("two", "Const", value=scalar_const(2.0))
             + node("ten", "Const", value=scalar_const(10.0))
             + node("tb", "Mul", ["sw:1", "two"])
             + node("fb", "Add", ["sw:0", "ten"])
             + node("merged", "Merge", ["fb", "tb"])
             + node("out", "Identity", ["merged"]))
        p = str(tmp_path / "cond.pb")
        open(p, "wb").write(g)
        return p

    def test_cond_selects_by_predicate(self, tmp_path):
        from bigdl_tpu.interop import load_tf_graph
        m = load_tf_graph(self._cond_graph(tmp_path),
                          inputs=["x", "pred"], outputs=["out"])
        x = np.array([1.0, 2.0], np.float32)
        t, _ = m.apply({}, {}, {"x": x, "pred": np.array(True)})
        f, _ = m.apply({}, {}, {"x": x, "pred": np.array(False)})
        np.testing.assert_allclose(np.asarray(t), x * 2)
        np.testing.assert_allclose(np.asarray(f), x + 10)

    def test_cond_with_traced_predicate_under_jit(self, tmp_path):
        from bigdl_tpu.interop import load_tf_graph
        m = load_tf_graph(self._cond_graph(tmp_path),
                          inputs=["x", "pred"], outputs=["out"])
        x = np.array([3.0], np.float32)
        fn = jax.jit(lambda x, p: m.apply({}, {},
                                          {"x": x, "pred": p})[0])
        np.testing.assert_allclose(np.asarray(fn(x, True)), x * 2)
        np.testing.assert_allclose(np.asarray(fn(x, False)), x + 10)

    def test_malformed_loop_frame_rejected(self, tmp_path):
        # a lone Enter with no LoopCond is not a valid while frame; the
        # loader (which now reconstructs real loops) rejects it up front
        from bigdl_tpu.interop import load_tf_graph
        from bigdl_tpu.utils import protowire as pw
        g = (pw.enc_bytes(1, pw.enc_str(1, "x")
                          + pw.enc_str(2, "Placeholder"))
             + pw.enc_bytes(1, pw.enc_str(1, "e") + pw.enc_str(2, "Enter")
                            + pw.enc_str(3, "x")))
        p = str(tmp_path / "loop.pb")
        open(p, "wb").write(g)
        with pytest.raises(NotImplementedError, match="LoopCond"):
            load_tf_graph(p, inputs=["x"], outputs=["e"])


class TestAuxReviewFixes:
    """Regressions for the round-2 aux review findings."""

    def test_lbfgs_survives_rejected_first_pair(self):
        # first (s, y) pair violates curvature (crafted gradient flip);
        # the optimizer must keep moving (used to freeze forever)
        lb = optim.LBFGS(history=4, learning_rate=0.1)
        params = {"w": jnp.asarray([1.0, -1.0, 2.0])}
        st = lb.init_state(params)
        grads = [jnp.asarray([2.0, 2.0, 2.0]),    # step 0
                 jnp.asarray([4.0, 4.0, 4.0]),    # s.y < 0 vs step 0 dir
                 jnp.asarray([1.0, 1.0, 1.0]),
                 jnp.asarray([0.5, 0.5, 0.5])]
        prev = params["w"]
        for i, g in enumerate(grads):
            params, st = lb.update({"w": g}, params, st, 0.1, i)
        assert not np.allclose(np.asarray(params["w"]),
                               np.asarray(prev)), "LBFGS froze"
        assert np.isfinite(np.asarray(params["w"])).all()

    def test_lbfgs_minimize_no_unevaluated_step(self):
        # a badly scaled objective where curvature keeps failing must not
        # commit an unevaluated exploding step
        def f(p):
            return jnp.sum(jnp.abs(p["w"]) ** 1.5)

        feval = jax.value_and_grad(f)
        p0 = {"w": jnp.asarray([2.0, -3.0])}
        p, loss, _ = optim.LBFGS().minimize(feval, p0, max_iter=20,
                                            max_ls=4)
        assert np.isfinite(loss)
        assert loss <= float(f(p0)) + 1e-9

    def test_imported_random_inits_differ_per_node(self, tmp_path):
        from bigdl_tpu.interop import load_tf_graph
        from bigdl_tpu.utils import protowire as pw



        g = b""
        for name in ("v1", "v2"):
            g += node(f"{name}/shape", "Const", value=shape_const([4, 4]))
            g += node(f"{name}/init", "TruncatedNormal",
                      [f"{name}/shape"])
            g += node(name, "VariableV2")
            g += node(f"{name}/assign", "Assign", [name, f"{name}/init"])
        g += node("out", "Add", ["v1", "v2"])
        p = str(tmp_path / "g.pb")
        open(p, "wb").write(g)
        m = load_tf_graph(p, inputs=[], outputs=["out"])
        v1, v2 = np.asarray(m._var_init["v1"]), np.asarray(m._var_init["v2"])
        assert v1.shape == (4, 4)
        assert not np.allclose(v1, v2), "same-shape inits byte-identical"

    def test_dilated_conv2d_attr_respected(self):
        from bigdl_tpu.ops import get_op
        x = np.random.RandomState(0).randn(1, 8, 8, 1).astype(np.float32)
        w = np.random.RandomState(1).randn(3, 3, 1, 1).astype(np.float32)
        conv = get_op("Conv2D")
        base = conv({"strides": [1, 1, 1, 1], "padding": b"VALID"}, x, w)
        dil = conv({"strides": [1, 1, 1, 1], "padding": b"VALID",
                    "dilations": [1, 2, 2, 1]}, x, w)
        assert base.shape == (1, 6, 6, 1)
        assert dil.shape == (1, 4, 4, 1)  # effective kernel 5x5

    def test_convert_cli_rejects_tf_to_bigdl_before_load(self, tmp_path):
        from bigdl_tpu.interop.convert_model import main as convert
        with pytest.raises(SystemExit):
            convert(["--from", "tensorflow", "--to", "bigdl",
                     "--input", str(tmp_path / "missing.pb"),
                     "--output", str(tmp_path / "x.bigdl"),
                     "--inputs", "a", "--outputs", "b"])
