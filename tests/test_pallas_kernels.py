"""Fused pallas kernel parity + activation-memory gates (round-10,
the HBM-floor PR).

Covers the ISSUE-8 acceptance surface:
- fused LSTM-cell and embedding-bag kernels gated bitwise-or-tolerance
  (forward AND gradient) against the XLA baseline, f32 and bf16, odd
  shapes (non-multiple-of-128 hidden/feature dims, empty bags,
  single-row batches), running the REAL kernel bodies under pallas
  interpret mode on CPU;
- the ``supported()`` fallback contract: unsupported shapes/dtypes
  silently take the XLA path with IDENTICAL (bitwise) results;
- ``Config.kernel_impl`` / ``BIGDL_TPU_KERNEL_IMPL`` resolution via
  ``Engine.kernel_impl()``;
- K∈{1,4} parity inside the fused-dispatch driver with the kernels
  engaged (the same discipline as tests/test_fused_step.py);
- ``Optimizer.set_activation_memory``: provably inert when off
  (bitwise loss sequence, equal dispatch count), exact-math for the
  remat policies, activation-dtype-only for bf16 (params stay f32).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn.recurrent import LSTM, Recurrent
from bigdl_tpu.nn.sparse import (COOBatch, LookupTableSparse,
                                 SparseLinear, coo_spmm)
from bigdl_tpu.ops import pallas_embed, pallas_lstm, resolve_kernel_impl
from bigdl_tpu.optim.optimizer import LocalOptimizer


def xla_lstm_cell(zx, h, c, w_t, fb=0.0):
    """The reference chain ``LSTM.step_hoisted`` lowers to."""
    z = zx + h @ w_t
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i, f = jax.nn.sigmoid(i), jax.nn.sigmoid(f + fb)
    g, o = jnp.tanh(g), jax.nn.sigmoid(o)
    c_new = f * c + i * g
    return o * jnp.tanh(c_new), c_new


def xla_bag(rows, cols, vals, table, n):
    g = jnp.take(table, cols, axis=0) * vals[:, None]
    return jax.ops.segment_sum(g, rows, num_segments=n)


def _leaves_close(a, b, rtol, atol):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# ===========================================================================
# fused LSTM cell (ops/pallas_lstm.py)
# ===========================================================================
class TestLSTMCellParity:
    CASES = [
        # (N, H, dtype, fwd_tol, grad_tol) — odd (non-128-multiple)
        # hidden, single-row batch, the PTB shape, lane-aligned bf16
        (5, 130, jnp.float32, 1e-5, 1e-4),
        (1, 64, jnp.float32, 1e-5, 1e-4),
        (20, 650, jnp.float32, 1e-4, 1e-3),
        (8, 128, jnp.bfloat16, 3e-2, 2e-1),
    ]

    @pytest.mark.parametrize("N,H,dtype,ftol,gtol", CASES)
    def test_forward_and_grad_match_xla(self, N, H, dtype, ftol, gtol):
        assert pallas_lstm.supported(N, H, dtype)
        rng = np.random.default_rng(N * 1000 + H)
        mk = lambda *s: jnp.asarray(  # noqa: E731
            rng.normal(0, 0.5, s).astype(np.float32)).astype(dtype)
        zx, h, c = mk(N, 4 * H), mk(N, H), mk(N, H)
        w = mk(H, 4 * H)

        hp, cp = jax.jit(
            lambda *a: pallas_lstm.lstm_cell(*a, forget_bias=1.0))(
                zx, h, c, w)
        hx, cx = xla_lstm_cell(*(a.astype(jnp.float32)
                                 for a in (zx, h, c, w)), fb=1.0)
        _leaves_close((hp, cp), (hx, cx), rtol=ftol, atol=ftol)

        def loss_p(zx, h, c, w):
            a, b = pallas_lstm.lstm_cell(zx, h, c, w, forget_bias=1.0)
            return (a.astype(jnp.float32) ** 2).sum() \
                + (b.astype(jnp.float32) * 1.5).sum()

        def loss_x(zx, h, c, w):
            a, b = xla_lstm_cell(zx, h, c, w, 1.0)
            return (a ** 2).sum() + (b * 1.5).sum()

        gp = jax.jit(jax.grad(loss_p, argnums=(0, 1, 2, 3)))(zx, h, c, w)
        gx = jax.grad(loss_x, argnums=(0, 1, 2, 3))(
            *(a.astype(jnp.float32) for a in (zx, h, c, w)))
        _leaves_close(gp, gx, rtol=gtol, atol=gtol)

    def test_recurrent_scan_parity_with_grad(self):
        """End-to-end through Recurrent's lax.scan: the fused cell and
        the XLA cell produce the same sequence output and the same
        parameter gradients."""
        rng = np.random.default_rng(3)
        N, T, D, H = 4, 6, 10, 32
        x = jnp.asarray(rng.normal(0, 1, (N, T, D)).astype(np.float32))
        outs, grads = {}, {}
        for impl in ("xla", "pallas"):
            rec = Recurrent(LSTM(D, H, forget_bias=1.0, impl=impl))
            p, _ = rec.init(jax.random.PRNGKey(0))
            outs[impl], _ = jax.jit(
                lambda p, x: rec.apply(p, {}, x))(p, x)
            grads[impl] = jax.jit(jax.grad(
                lambda p, x: rec.apply(p, {}, x)[0].sum()))(p, x)
        _leaves_close(outs["pallas"], outs["xla"], 1e-5, 1e-5)
        _leaves_close(grads["pallas"], grads["xla"], 1e-4, 1e-4)


class TestLSTMSupportedGate:
    def test_dtype_and_budget_gates(self):
        assert pallas_lstm.supported(8, 128, jnp.float32)
        assert pallas_lstm.supported(8, 650, jnp.bfloat16)
        assert not pallas_lstm.supported(8, 128, jnp.int32)
        # H=1100 -> lane-padded weight panel over the element budget
        assert not pallas_lstm.supported(8, 1100, jnp.float32)
        assert not pallas_lstm.supported(0, 128, jnp.float32)

    def test_unsupported_shape_silently_takes_xla_path_bitwise(self):
        """impl="pallas" on a shape supported() rejects must produce
        BITWISE-identical results to impl="xla" — proof the fallback is
        the untouched baseline, not a second implementation."""
        rng = np.random.default_rng(7)
        N, T, D, H = 2, 3, 6, 1100  # over the weight-panel budget
        assert not pallas_lstm.supported(N, H, jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (N, T, D)).astype(np.float32))
        ys = {}
        for impl in ("xla", "pallas"):
            rec = Recurrent(LSTM(D, H, impl=impl))
            p, _ = rec.init(jax.random.PRNGKey(1))
            y, _ = jax.jit(lambda p, x: rec.apply(p, {}, x))(p, x)
            ys[impl] = np.asarray(y)
        assert np.array_equal(ys["pallas"], ys["xla"])


# ===========================================================================
# fused embedding-bag (ops/pallas_embed.py)
# ===========================================================================
class TestEmbeddingBagParity:
    CASES = [
        # (name, N, V, D, nnz, dtype, tol)
        ("aligned", 4, 64, 128, 9, jnp.float32, 1e-5),
        ("wide_d1", 8, 100, 1, 40, jnp.float32, 1e-5),
        ("odd_d", 5, 30, 10, 17, jnp.float32, 1e-5),
        ("single_row", 1, 20, 8, 5, jnp.float32, 1e-5),
        ("bf16", 6, 50, 16, 32, jnp.bfloat16, 5e-2),
    ]

    @pytest.mark.parametrize("name,N,V,D,nnz,dtype,tol", CASES)
    def test_forward_and_grad_match_xla(self, name, N, V, D, nnz, dtype,
                                        tol):
        # bf16 tables are refused by the gate (the v5e compiler cannot
        # slice one row out of a packed bf16 sublane pair — see
        # supported()); the kernel body itself stays parity-tested in
        # interpret mode so the gate can be lifted without a rewrite
        assert pallas_embed.supported(nnz, N, (V, D), dtype) \
            == (dtype == jnp.float32)
        rng = np.random.default_rng(abs(hash(name)) % 2 ** 31)
        rows = jnp.asarray(rng.integers(0, N, nnz).astype(np.int32))
        cols = jnp.asarray(rng.integers(0, V, nnz).astype(np.int32))
        vals = jnp.asarray(rng.normal(0, 1, nnz).astype(np.float32))
        table = jnp.asarray(
            rng.normal(0, 1, (V, D)).astype(np.float32)).astype(dtype)

        got = jax.jit(lambda r, c, v, t: pallas_embed.embedding_bag_coo(
            r, c, v, t, N))(rows, cols, vals, table)
        want = xla_bag(rows, cols, vals, table, N)
        assert got.dtype == want.dtype
        _leaves_close(got, want, tol, tol)
        if dtype == jnp.bfloat16:
            # bf16 values too: the promoted output dtype must track the
            # ORIGINAL operand dtypes exactly like the XLA chain
            vb = vals.astype(jnp.bfloat16)
            got_b = pallas_embed.embedding_bag_coo(rows, cols, vb, table,
                                                   N)
            assert got_b.dtype == xla_bag(rows, cols, vb, table, N).dtype

        def loss_p(v, t):
            out = pallas_embed.embedding_bag_coo(rows, cols, v, t, N)
            return (out.astype(jnp.float32) ** 2).sum()

        def loss_x(v, t):
            return (xla_bag(rows, cols, v, t, N).astype(
                jnp.float32) ** 2).sum()

        gp = jax.jit(jax.grad(loss_p, argnums=(0, 1)))(vals, table)
        gx = jax.grad(loss_x, argnums=(0, 1))(vals, table)
        _leaves_close(gp, gx, tol * 10, tol * 10)

    def test_unsorted_rows_duplicates_and_padding(self):
        """The VMEM accumulator is order-independent: unsorted rows,
        duplicate (row, col) pairs and trailing (0, 0, 0.0) padding
        entries — exactly what batch_sparse_samples emits — all
        accumulate like the XLA segment-sum."""
        rows = jnp.asarray([3, 0, 3, 1, 0, 0, 0], jnp.int32)
        cols = jnp.asarray([2, 5, 2, 1, 0, 0, 0], jnp.int32)
        vals = jnp.asarray([1.0, 2.0, 0.5, -1.0, 3.0, 0.0, 0.0],
                           jnp.float32)
        table = jnp.asarray(
            np.random.default_rng(0).normal(0, 1, (8, 4)).astype(
                np.float32))
        got = pallas_embed.embedding_bag_coo(rows, cols, vals, table, 5)
        want = xla_bag(rows, cols, vals, table, 5)
        _leaves_close(got, want, 1e-5, 1e-5)
        # row 2 and 4 are empty segments -> exact zeros
        assert float(jnp.abs(got[2]).sum()) == 0.0
        assert float(jnp.abs(got[4]).sum()) == 0.0

    def test_sparse_layers_parity(self):
        rng = np.random.default_rng(11)
        coo = COOBatch(
            jnp.asarray(rng.integers(0, 5, 20).astype(np.int32)),
            jnp.asarray(rng.integers(0, 50, 20).astype(np.int32)),
            jnp.asarray(rng.normal(0, 1, 20).astype(np.float32)),
            (5, 50))
        for combiner in ("sum", "mean"):
            outs = {}
            for impl in ("xla", "pallas"):
                m = LookupTableSparse(50, 16, combiner, impl=impl)
                p, _ = m.init(jax.random.PRNGKey(2))
                outs[impl], _ = jax.jit(
                    lambda p, c: m.apply(p, {}, c))(p, coo)
            _leaves_close(outs["pallas"], outs["xla"], 1e-5, 1e-5)
        outs = {}
        for impl in ("xla", "pallas"):
            m = SparseLinear(50, 3, impl=impl)
            p, _ = m.init(jax.random.PRNGKey(3))
            outs[impl], _ = jax.jit(lambda p, c: m.apply(p, {}, c))(p, coo)
        _leaves_close(outs["pallas"], outs["xla"], 1e-5, 1e-5)


class TestEmbedSupportedGate:
    def test_gates(self):
        assert pallas_embed.supported(64, 8192, (100_000, 1),
                                      jnp.float32)  # the wide path
        assert not pallas_embed.supported(64, 8, (10, 4), jnp.int32)
        # D > 128 and not lane-aligned
        assert not pallas_embed.supported(64, 8, (10, 200), jnp.float32)
        # output accumulator over the VMEM element budget
        assert not pallas_embed.supported(64, 100_000, (10, 128),
                                          jnp.float32)
        assert not pallas_embed.supported(0, 8, (10, 4), jnp.float32)

    def test_unsupported_falls_back_bitwise(self):
        rng = np.random.default_rng(5)
        # D=200: not lane-aligned, >128 -> supported() rejects
        coo = COOBatch(
            jnp.asarray(rng.integers(0, 4, 12).astype(np.int32)),
            jnp.asarray(rng.integers(0, 9, 12).astype(np.int32)),
            jnp.asarray(rng.normal(0, 1, 12).astype(np.float32)),
            (4, 9))
        table = jnp.asarray(rng.normal(0, 1, (9, 200)).astype(np.float32))
        assert not pallas_embed.supported(12, 4, table.shape, table.dtype)
        a = np.asarray(coo_spmm(coo, table, impl="pallas"))
        b = np.asarray(coo_spmm(coo, table, impl="xla"))
        assert np.array_equal(a, b)


# ===========================================================================
# kernel_impl resolution (Config / env / Engine)
# ===========================================================================
@pytest.fixture
def _kernel_impl_guard():
    prev = Engine._state.kernel_impl
    yield
    Engine._state.kernel_impl = prev


class TestKernelImplResolution:
    def test_engine_default_flows_from_config(self, _kernel_impl_guard):
        from bigdl_tpu.utils.config import Config
        assert Config().kernel_impl == "auto"
        # auto on a CPU host resolves to xla (interpret kernels are
        # emulation, not a speedup)
        Engine.set_kernel_impl("auto")
        assert resolve_kernel_impl(None) == "xla"

    def test_engine_override_and_layer_override(self, _kernel_impl_guard):
        Engine.set_kernel_impl("pallas")
        assert resolve_kernel_impl(None) == "pallas"
        assert resolve_kernel_impl("xla") == "xla"  # layer arg wins
        Engine.set_kernel_impl("xla")
        assert resolve_kernel_impl(None) == "xla"
        assert resolve_kernel_impl("pallas") == "pallas"

    def test_invalid_values_rejected(self, _kernel_impl_guard):
        with pytest.raises(ValueError):
            Engine.set_kernel_impl("mosaic")
        with pytest.raises(ValueError):
            resolve_kernel_impl("cuda")

    def test_env_var_reaches_config(self, monkeypatch):
        from bigdl_tpu.utils.config import Config
        monkeypatch.setenv("BIGDL_TPU_KERNEL_IMPL", "pallas")
        assert Config.from_env().kernel_impl == "pallas"

    def test_engine_kernel_impl_engages_layers(self, _kernel_impl_guard):
        """No per-layer impl arg: the Engine-level knob alone flips the
        COO path onto the kernel (same numbers either way — this pins
        the RESOLUTION plumbing, parity is gated above)."""
        rng = np.random.default_rng(13)
        coo = COOBatch(
            jnp.asarray(rng.integers(0, 4, 10).astype(np.int32)),
            jnp.asarray(rng.integers(0, 20, 10).astype(np.int32)),
            jnp.asarray(rng.normal(0, 1, 10).astype(np.float32)),
            (4, 20))
        table = jnp.asarray(rng.normal(0, 1, (20, 8)).astype(np.float32))
        Engine.set_kernel_impl("xla")
        base = np.asarray(coo_spmm(coo, table))
        Engine.set_kernel_impl("pallas")
        fused = np.asarray(coo_spmm(coo, table))
        np.testing.assert_allclose(fused, base, rtol=1e-5, atol=1e-5)


# ===========================================================================
# K∈{1,4} parity inside the fused-dispatch driver (acceptance bar)
# ===========================================================================
class RecordingSummary:
    def __init__(self):
        self.rows = []

    def add_train_step(self, step, loss, lr, throughput):
        self.rows.append((step, loss, lr))

    def add_scalar(self, tag, value, step):
        pass

    def trigger_for(self, name):
        return None

    @property
    def losses(self):
        return np.array([l for _, l, _ in self.rows])


def _lm_samples(n=24, T=6, vocab=40, seed=0):
    rng = np.random.default_rng(seed)
    return [Sample(rng.integers(0, vocab, (T,)).astype(np.int32),
                   rng.integers(0, vocab, (T,)).astype(np.int32))
            for _ in range(n)]


def _run_lstm_driver(impl, k, iters=6):
    model = (nn.Sequential()
             .add(nn.LookupTable(40, 8))
             .add(Recurrent(LSTM(8, 32, impl=impl)))
             .add(nn.TimeDistributed(nn.Linear(32, 40)))
             .add(nn.LogSoftMax()))
    ds = DataSet.array(_lm_samples()) >> SampleToMiniBatch(8)
    rec = RecordingSummary()
    opt = (LocalOptimizer(
               model, ds,
               nn.TimeDistributedCriterion(nn.ClassNLLCriterion()))
           .set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
           .set_train_summary(rec)
           .set_steps_per_dispatch(k)
           .set_end_when(optim.max_iteration(iters)).set_seed(5))
    opt.optimize()
    return rec.losses, opt


def _sparse_samples(n=24, width=30, nnz=4, seed=0):
    from bigdl_tpu.dataset import SparseSample
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        idx = np.sort(rng.choice(width, nnz, replace=False))
        out.append(SparseSample(
            idx.astype(np.int32),
            rng.normal(0, 1, nnz).astype(np.float32), width,
            label=np.float32(rng.integers(0, 2))))
    return out


class _SparseToMiniBatch:
    """Minimal Transformer batching SparseSamples into COO minibatches
    (one fixed nnz bucket keeps every block signature identical)."""

    def __init__(self, batch_size, nnz_buckets):
        self.batch_size = batch_size
        self.nnz_buckets = nnz_buckets

    def __call__(self, it):
        from bigdl_tpu.dataset import batch_sparse_samples
        buf = []
        for s in it:
            buf.append(s)
            if len(buf) == self.batch_size:
                yield batch_sparse_samples(buf, self.nnz_buckets)
                buf = []


def _run_sparse_driver(impl, k, iters=6):
    class _BCE:
        def __init__(self):
            self.bce = nn.BCECriterion()

        def apply(self, out, y):
            return self.bce.apply(jax.nn.sigmoid(out[:, 0]), y)

    model = SparseLinear(30, 1, impl=impl)
    ds = DataSet.array(_sparse_samples()) >> _SparseToMiniBatch(8, [64])
    rec = RecordingSummary()
    opt = (LocalOptimizer(model, ds, _BCE())
           .set_optim_method(optim.SGD(learning_rate=0.5))
           .set_train_summary(rec)
           .set_steps_per_dispatch(k)
           .set_end_when(optim.max_iteration(iters)).set_seed(5))
    opt.optimize()
    return rec.losses, opt


class TestFusedDispatchDriverParity:
    def test_lstm_pallas_matches_xla_for_k1_and_k4(self):
        ref = {}
        for k in (1, 4):
            lx, _ = _run_lstm_driver("xla", k)
            lp, _ = _run_lstm_driver("pallas", k)
            assert len(lp) == len(lx) == 6
            np.testing.assert_allclose(lp, lx, rtol=2e-4, atol=2e-5)
            ref[k] = lp
        # K-invariance with the kernel engaged (driver contract)
        np.testing.assert_allclose(ref[1], ref[4], rtol=1e-5, atol=1e-6)

    def test_sparse_pallas_matches_xla_for_k1_and_k4(self):
        ref = {}
        for k in (1, 4):
            lx, _ = _run_sparse_driver("xla", k)
            lp, _ = _run_sparse_driver("pallas", k)
            assert len(lp) == len(lx) == 6
            np.testing.assert_allclose(lp, lx, rtol=2e-4, atol=2e-5)
            ref[k] = lp
        np.testing.assert_allclose(ref[1], ref[4], rtol=1e-5, atol=1e-6)


# ===========================================================================
# Optimizer.set_activation_memory
# ===========================================================================
def _run_mlp(policy, call=True, iters=6):
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (16,)).astype(np.float32),
                      np.int32(rng.integers(0, 4))) for _ in range(32)]
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                          nn.Linear(32, 4), nn.LogSoftMax())
    ds = DataSet.array(samples) >> SampleToMiniBatch(8)
    rec = RecordingSummary()
    opt = (LocalOptimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
           .set_train_summary(rec)
           .set_end_when(optim.max_iteration(iters)).set_seed(7))
    if call:
        opt.set_activation_memory(policy)
    opt.optimize()
    return rec.losses, opt


class TestActivationMemory:
    def test_off_is_provably_inert(self):
        """ISSUE-8 acceptance: bitwise loss sequence + equal dispatch
        count whether set_activation_memory was never called or called
        with "none"/None."""
        l_base, o_base = _run_mlp(None, call=False)
        for policy in (None, "none"):
            l_p, o_p = _run_mlp(policy)
            assert l_p.tolist() == l_base.tolist()  # bitwise
            assert o_p._dispatch_count == o_base._dispatch_count

    def test_remat_policies_are_exact_math(self):
        """Remat changes WHAT is stored, never what is computed: the
        loss trajectory and final params stay identical to float
        rounding (XLA may fuse the recomputed chain differently, so
        bitwise is graph-dependent — measured one-ulp-level deltas on
        some graphs; the math itself is exact)."""
        l_base, o_base = _run_mlp(None, call=False)
        for policy in ("dots", "full"):
            l_p, o_p = _run_mlp(policy)
            np.testing.assert_allclose(l_p, l_base, rtol=1e-6,
                                       atol=1e-7, err_msg=policy)
            for a, b in zip(
                    jax.tree_util.tree_leaves(o_base.model._params),
                    jax.tree_util.tree_leaves(o_p.model._params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-7)

    def test_bf16_changes_activations_never_params_or_update(self):
        l_base, _ = _run_mlp(None, call=False)
        l_bf, o_bf = _run_mlp("bf16")
        assert l_bf.tolist() != l_base.tolist()  # numerics did change
        assert abs(l_bf[-1] - l_base[-1]) < 0.2  # ... but sanely
        for leaf in jax.tree_util.tree_leaves(o_bf.model._params):
            assert np.asarray(leaf).dtype == np.float32
        for leaf in jax.tree_util.tree_leaves(o_bf._final_opt_state):
            if hasattr(leaf, "dtype") and np.issubdtype(
                    np.asarray(leaf).dtype, np.floating):
                assert np.asarray(leaf).dtype == np.float32

    def test_combined_policies_and_validation(self):
        l_base, _ = _run_mlp(None, call=False)
        l_c, _ = _run_mlp("bf16+dots")
        assert abs(l_c[-1] - l_base[-1]) < 0.2
        with pytest.raises(ValueError):
            _run_mlp("fp8")

    def test_bf16_policy_conflicts_with_explicit_f32_compute(self):
        """An explicit non-bf16 compute dtype contradicts a bf16
        activation policy — refused loudly, never silently dropped."""
        rng = np.random.default_rng(1)
        samples = [Sample(rng.normal(0, 1, (8,)).astype(np.float32),
                          np.int32(0)) for _ in range(8)]
        model = nn.Sequential(nn.Linear(8, 2), nn.LogSoftMax())
        ds = DataSet.array(samples) >> SampleToMiniBatch(4)
        opt = (LocalOptimizer(model, ds, nn.ClassNLLCriterion())
               .set_compute_dtype(jnp.float32)
               .set_activation_memory("bf16")
               .set_end_when(optim.max_iteration(1)))
        with pytest.raises(ValueError, match="conflicts"):
            opt.optimize()


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
