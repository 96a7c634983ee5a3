"""Round-5 closures: the last reference trivia (VERDICT r4 missing
#2-4 — FloorMod/BiasAddV1 TF ops, Kv2Tensor feature column,
ChannelScaledNormalizer/RandomResize augmentations) and the r4 advisor
fixes (LookupTableSparse raw-weight mean, ConvLSTMPeephole3D checkpoint
guard, SGD velocity dtype promotion)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops.registry import get_op


class TestLastTFOps:
    def test_floor_mod_sign_follows_divisor(self):
        # floored modulo (TF FloorMod): result carries the DIVISOR's
        # sign — the property that distinguishes it from TruncateMod
        a = jnp.asarray([7.0, -7.0, 7.0, -7.0])
        b = jnp.asarray([3.0, 3.0, -3.0, -3.0])
        out = np.asarray(get_op("FloorMod")({}, a, b))
        np.testing.assert_allclose(out, [1.0, 2.0, -2.0, -1.0])
        got = np.asarray(get_op("FloorMod")(
            {}, jnp.asarray([7, -7], jnp.int32), jnp.asarray(3, jnp.int32)))
        np.testing.assert_array_equal(got, [1, 2])

    def test_bias_add_v1(self):
        x = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
        b = jnp.asarray([1.0, 2.0, 3.0, 4.0])
        out = np.asarray(get_op("BiasAddV1")({}, x, b))
        np.testing.assert_allclose(out, np.asarray(x) + np.asarray(b))


class TestKv2Tensor:
    def test_dense(self):
        from bigdl_tpu.dataset import Kv2Tensor
        op = Kv2Tensor()
        out = op(["0:1.5,2:2.0", "1:3.0", ""], fea_len=4)
        want = np.zeros((3, 4), np.float32)
        want[0, 0], want[0, 2], want[1, 1] = 1.5, 2.0, 3.0
        np.testing.assert_allclose(out, want)

    def test_sparse_matches_dense(self):
        from bigdl_tpu.dataset import Kv2Tensor
        col = ["0:1.0,3:4.0", "2:-2.5"]
        dense = Kv2Tensor(trans_type=0)(col, fea_len=5)
        coo = Kv2Tensor(trans_type=1)(col, fea_len=5)
        assert coo.dense_shape == (2, 5)
        np.testing.assert_allclose(np.asarray(coo.to_dense()), dense)

    def test_custom_delimiters_and_range_check(self):
        from bigdl_tpu.dataset import Kv2Tensor
        out = Kv2Tensor(kv_delimiter=";", item_delimiter="=")(
            ["1=2.0;0=1.0"], fea_len=2)
        np.testing.assert_allclose(out, [[1.0, 2.0]])
        with pytest.raises(ValueError):
            Kv2Tensor()(["9:1.0"], fea_len=4)


class TestNewAugmentations:
    def _feature(self, h, w):
        from bigdl_tpu.transform import ImageFeature
        rng = np.random.default_rng(0)
        f = ImageFeature()
        f.image = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
        return f

    def test_channel_scaled_normalizer(self):
        from bigdl_tpu.transform import ChannelScaledNormalizer
        f = self._feature(4, 5)
        img = f.image.copy()
        out = ChannelScaledNormalizer(10, 20, 30, 0.5).transform(f)
        want = (img - np.asarray([10, 20, 30], np.float32)) * 0.5
        np.testing.assert_allclose(out.image, want, rtol=1e-6)

    def test_random_resize_short_edge_in_range(self):
        from bigdl_tpu.transform import RandomResize
        t = RandomResize(8, 16, seed=3)
        for _ in range(5):
            f = self._feature(20, 30)
            out = t.transform(f)
            h, w = out.image.shape[:2]
            assert 8 <= min(h, w) <= 16
            # aspect ratio preserved (int truncation tolerance)
            assert abs(w / h - 30 / 20) < 0.15

    def test_random_resize_portrait(self):
        from bigdl_tpu.transform import RandomResize
        f = self._feature(40, 10)
        out = RandomResize(12, 12, seed=0).transform(f)
        assert out.image.shape[:2] == (48, 12)


class TestPallasPoolVmemGate:
    def test_supported_gates_large_spatial_blocks(self):
        # the per-block element gate follows what the v5e compiler of
        # the installed toolchain admits (pallas_pool.supported
        # docstring; re-asked in tests/test_chip_compile.py): blocks
        # past it must route to the reduce_window fallback
        from bigdl_tpu.ops.pallas_pool import supported
        k, s = (3, 3), (2, 2)
        pads = ((0, 1), (0, 1))
        assert not supported((256, 224, 224, 64), k, s, pads)
        assert not supported((256, 112, 112, 192), k, s, pads)
        assert supported((256, 112, 112, 64), k, s, pads)
        assert supported((256, 28, 28, 480), k, s, pads)
        assert supported((256, 14, 14, 832), k, s, pads)
        # structural rejections unchanged
        assert not supported((256, 28, 28, 64), (2, 2), (3, 3), pads)

    def test_fallback_path_still_correct(self):
        import jax
        import jax.numpy as jnp
        from bigdl_tpu.ops.pallas_pool import (
            maxpool_nhwc_with_pallas_bwd, supported)
        rng = np.random.default_rng(0)
        # a gated shape (96*96*256 = 2.4M elements, over the gate):
        # must silently take reduce_window fwd + select-and-scatter bwd
        shape = (2, 96, 96, 192)
        dims, strides = (1, 3, 3, 1), (1, 2, 2, 1)
        pads = ((0, 0), (0, 1), (0, 1), (0, 0))
        assert not supported(shape, (3, 3), (2, 2), (pads[1], pads[2]))
        x = jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))

        def f(x):
            return maxpool_nhwc_with_pallas_bwd(
                x, dims, strides, pads).sum()

        y, g = jax.value_and_grad(f)(x)
        want = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims,
                                     strides, pads)
        np.testing.assert_allclose(float(y), float(want.sum()), rtol=1e-6)
        assert g.shape == x.shape and np.isfinite(np.asarray(g)).all()


class TestScanHoisting:
    """Input-projection hoisting + unroll are exact-math scan
    transformations (Recurrent docstring); every hoist-capable cell
    must match the plain step path bit-for-tolerance."""

    def _no_hoist(self, cell):
        class NoHoist:
            def __init__(self, c):
                self.c = c

            def __getattr__(self, k):
                return getattr(self.c, k)

            def hoist(self, params, xs):
                return None
        return NoHoist(cell)

    @pytest.mark.parametrize("make", [
        lambda R: R.RnnCell(5, 6),
        lambda R: R.LSTM(5, 6),
        lambda R: R.GRU(5, 6),
        lambda R: R.MultiRNNCell([R.LSTM(5, 6), R.GRU(6, 4)]),
    ], ids=["rnn", "lstm", "gru", "stack"])
    @pytest.mark.parametrize("unroll", [1, 4])
    def test_hoisted_matches_plain(self, make, unroll):
        from bigdl_tpu.nn import recurrent as R
        cell = make(R)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (3, 7, 5)).astype(np.float32))
        r = R.Recurrent(cell, unroll=unroll)
        p, s = r.init(jax.random.PRNGKey(0))
        y, _ = r.apply(p, s, x)
        ref = R.Recurrent(self._no_hoist(cell))
        y_ref, _ = ref.apply(p, s, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5)

    def test_duck_typed_cell_without_hoist_api(self):
        # the Cell contract is duck-typed (quantized cells, user cells
        # predating the hoist API provide only step/initial_hidden);
        # Recurrent must not require the new methods
        from bigdl_tpu.nn import recurrent as R

        class MinimalCell:
            hidden_size = 4

            def initial_hidden(self, batch_size):
                return jnp.zeros((batch_size, 4))

            def step(self, params, x_t, h):
                h2 = jnp.tanh(x_t @ params["w"] + h)
                return h2, h2

        r = R.Recurrent(MinimalCell())
        p = {"w": jnp.ones((3, 4)) * 0.1}
        y, _ = r.apply(p, {}, jnp.ones((2, 5, 3)))
        assert y.shape == (2, 5, 4)
        assert np.isfinite(np.asarray(y)).all()

        # and stacked: MultiRNNCell's layer-0 hoist must duck-type too
        class MC(MinimalCell):
            def initial_hidden(self, batch_size):
                return jnp.zeros((batch_size, 4))

        stack = R.Recurrent(R.MultiRNNCell([MC(), R.GRU(4, 3)]))
        g = R.GRU(4, 3)
        gp, _ = g.init(jax.random.PRNGKey(1))
        y2, _ = stack.apply({"0": p, "1": gp}, {}, jnp.ones((2, 5, 3)))
        assert y2.shape == (2, 5, 3)

    def test_grad_flows_through_hoisted_path(self):
        from bigdl_tpu.nn import recurrent as R
        r = R.Recurrent(R.LSTM(5, 6), unroll=2)
        p, s = r.init(jax.random.PRNGKey(0))
        x = jnp.ones((2, 7, 5))

        def loss(p):
            y, _ = r.apply(p, s, x)
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(p)
        assert np.isfinite(np.asarray(g["weight"])).all()
        assert float(jnp.abs(g["weight"]).sum()) > 0


class TestHoistedScanUnderDP:
    def test_ptb_trains_data_parallel_on_mesh(self, devices):
        """The hoisted+unrolled LSTM must compose with GSPMD data
        parallelism (batch-sharded inputs, replicated params)."""
        from functools import partial
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from bigdl_tpu import nn, optim
        from bigdl_tpu.models.rnn import ptb_model

        mesh = Mesh(np.array(devices), ("data",))
        model = ptb_model(200, 32, 32, 2, scan_unroll=5)
        crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
        method = optim.SGD(learning_rate=0.1, momentum=0.9)
        p, s = model.init(jax.random.PRNGKey(0))
        os_ = method.init_state(p)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 200, (32, 12)).astype(np.int32))
        y = jnp.asarray(rng.integers(0, 200, (32, 12)).astype(np.int32))
        data_sh = NamedSharding(mesh, P("data"))
        repl = NamedSharding(mesh, P())
        x, y = jax.device_put(x, data_sh), jax.device_put(y, data_sh)
        p = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), p)
        os_ = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), os_)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(p, os_, x, y, it):
            def loss_fn(p):
                out, _ = model.apply(p, s, x)
                return crit.apply(out, y)
            loss, g = jax.value_and_grad(loss_fn)(p)
            p, os_ = method.update(g, p, os_, 0.1, it)
            return p, os_, loss

        losses = []
        for i in range(20):
            p, os_, loss = step(p, os_, x, y, i)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()


class TestAdvisorFixes:
    def test_convlstm3d_checkpoint_guard(self):
        from bigdl_tpu.nn.recurrent import ConvLSTMPeephole3D
        cell = ConvLSTMPeephole3D(2, 3, spatial=(2, 4, 4))
        old = ConvLSTMPeephole3D(2, 3, spatial=(2, 4, 4),
                                 with_peephole=False)
        params, _ = old.init(jax.random.PRNGKey(0))
        x = jnp.zeros((1, 2, 2, 4, 4))
        hidden = cell.initial_hidden(1)
        with pytest.raises(KeyError, match="with_peephole=False"):
            cell.step(params, x, hidden)

    def test_sgd_velocity_stays_f32_under_bf16_grads(self):
        from bigdl_tpu import optim
        m = optim.SGD(learning_rate=0.1, momentum=0.9)
        params = {"w": jnp.ones((4,), jnp.float32)}
        state = m.init_state(params)
        assert state["velocity"]["w"].dtype == jnp.float32
        grads = {"w": jnp.full((4,), 0.5, jnp.bfloat16)}
        _, state = m.update(grads, params, state, 0.1, 0)
        assert state["velocity"]["w"].dtype == jnp.float32
