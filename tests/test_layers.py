"""Per-layer unit tests (reference: ``TEST/nn/`` — one Spec per layer,
deterministic seeds, numeric gradient checks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn


def rng(i=0):
    return jax.random.PRNGKey(i)


class TestLinear:
    def test_forward_shape_and_value(self):
        layer = nn.Linear(4, 3).initialize(0)
        x = jnp.ones((2, 4))
        y = layer.forward(x)
        assert y.shape == (2, 3)
        w, b = layer._params["weight"], layer._params["bias"]
        np.testing.assert_allclose(y, x @ w.T + b, rtol=1e-6)

    def test_no_bias(self):
        layer = nn.Linear(4, 3, with_bias=False).initialize(0)
        assert "bias" not in layer._params

    def test_grad_matches_numeric(self):
        layer = nn.Linear(3, 2).initialize(1)
        x = jax.random.normal(rng(2), (5, 3))

        def loss(params):
            y, _ = layer.apply(params, {}, x)
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(layer._params)
        # numeric check on one weight element
        eps = 1e-3
        p0 = layer._params
        pp = jax.tree_util.tree_map(lambda a: a.copy(), p0)
        pp["weight"] = pp["weight"].at[0, 0].add(eps)
        pm = jax.tree_util.tree_map(lambda a: a.copy(), p0)
        pm["weight"] = pm["weight"].at[0, 0].add(-eps)
        num = (loss(pp) - loss(pm)) / (2 * eps)
        np.testing.assert_allclose(g["weight"][0, 0], num, rtol=1e-2)


class TestConv:
    def test_shapes(self):
        conv = nn.SpatialConvolution(3, 8, 3, 3, pad_w=1, pad_h=1).initialize(0)
        y = conv.forward(jnp.ones((2, 3, 16, 16)))
        assert y.shape == (2, 8, 16, 16)

    def test_stride(self):
        conv = nn.SpatialConvolution(3, 8, 3, 3, stride_w=2, stride_h=2).initialize(0)
        y = conv.forward(jnp.ones((2, 3, 17, 17)))
        assert y.shape == (2, 8, 8, 8)

    def test_groups(self):
        conv = nn.SpatialConvolution(4, 8, 3, 3, n_group=2).initialize(0)
        assert conv._params["weight"].shape == (8, 2, 3, 3)
        y = conv.forward(jnp.ones((1, 4, 8, 8)))
        assert y.shape == (1, 8, 6, 6)

    def test_known_value(self):
        conv = nn.SpatialConvolution(1, 1, 2, 2, with_bias=False).initialize(0)
        conv._params["weight"] = jnp.ones((1, 1, 2, 2))
        x = jnp.arange(9.0).reshape(1, 1, 3, 3)
        y = conv.forward(x)
        np.testing.assert_allclose(y[0, 0], jnp.array([[8., 12.], [20., 24.]]))

    def test_nhwc(self):
        conv = nn.SpatialConvolution(3, 8, 3, 3, format="NHWC").initialize(0)
        y = conv.forward(jnp.ones((2, 16, 16, 3)))
        assert y.shape == (2, 14, 14, 8)


class TestPooling:
    def test_max(self):
        pool = nn.SpatialMaxPooling(2, 2)
        x = jnp.arange(16.0).reshape(1, 1, 4, 4)
        y = pool.forward(x)
        np.testing.assert_allclose(y[0, 0], jnp.array([[5., 7.], [13., 15.]]))

    def test_avg(self):
        pool = nn.SpatialAveragePooling(2, 2)
        x = jnp.arange(16.0).reshape(1, 1, 4, 4)
        y = pool.forward(x)
        np.testing.assert_allclose(y[0, 0], jnp.array([[2.5, 4.5], [10.5, 12.5]]))

    def test_ceil_mode(self):
        pool = nn.SpatialMaxPooling(2, 2, ceil_mode=True)
        y = pool.forward(jnp.ones((1, 1, 5, 5)))
        assert y.shape == (1, 1, 3, 3)
        floor = nn.SpatialMaxPooling(2, 2).forward(jnp.ones((1, 1, 5, 5)))
        assert floor.shape == (1, 1, 2, 2)


class TestBatchNorm:
    def test_normalizes(self):
        bn = nn.SpatialBatchNormalization(4).initialize(0)
        x = jax.random.normal(rng(0), (8, 4, 5, 5)) * 3 + 2
        y = bn.forward(x)
        assert abs(float(jnp.mean(y))) < 1e-4
        assert abs(float(jnp.std(y)) - 1.0) < 1e-2

    def test_running_stats_updated(self):
        bn = nn.SpatialBatchNormalization(4).initialize(0)
        x = jax.random.normal(rng(1), (8, 4, 5, 5)) + 5.0
        bn.forward(x)
        assert float(jnp.mean(bn._state["running_mean"])) > 0.1

    def test_eval_uses_running(self):
        bn = nn.SpatialBatchNormalization(4).initialize(0)
        x = jax.random.normal(rng(2), (8, 4, 5, 5)) + 5.0
        bn.forward(x)
        bn.evaluate()
        y = bn.forward(x)
        # eval-mode output should NOT be zero-mean (running stats lag)
        assert abs(float(jnp.mean(y))) > 0.1


class TestDropout:
    def test_train_drops_and_scales(self):
        d = nn.Dropout(0.5)
        x = jnp.ones((100, 100))
        y = d.forward(x, rng=rng(0))
        frac_zero = float(jnp.mean(y == 0.0))
        assert 0.4 < frac_zero < 0.6
        nz = y[y != 0]
        np.testing.assert_allclose(nz, 2.0)

    def test_eval_identity(self):
        d = nn.Dropout(0.5).evaluate()
        x = jnp.ones((10, 10))
        np.testing.assert_allclose(d.forward(x), x)


_ROWWISE = [(nn.LogSoftMax, jax.nn.log_softmax), (nn.SoftMax, jax.nn.softmax)]
_ROWWISE_IDS = ["LogSoftMax", "SoftMax"]


class TestActivations:
    @pytest.mark.parametrize("layer,fn", [
        (nn.ReLU(), lambda x: np.maximum(x, 0)),
        (nn.Tanh(), np.tanh),
        (nn.Sigmoid(), lambda x: 1 / (1 + np.exp(-x))),
        (nn.ReLU6(), lambda x: np.clip(x, 0, 6)),
        (nn.SoftSign(), lambda x: x / (1 + np.abs(x))),
    ])
    def test_matches_numpy(self, layer, fn):
        x = np.linspace(-3, 8, 23).astype(np.float32)
        y = layer.forward(jnp.asarray(x))
        np.testing.assert_allclose(y, fn(x), rtol=1e-5, atol=1e-6)

    def test_logsoftmax_rows_sum_to_one(self):
        y = nn.LogSoftMax().forward(jax.random.normal(rng(0), (4, 7)))
        np.testing.assert_allclose(jnp.sum(jnp.exp(y), -1), 1.0, rtol=1e-5)

    @pytest.mark.parametrize("shape", [(7,), (4, 7), (3, 5, 7),
                                       (2, 3, 5, 7)],
                             ids=["rank1", "rank2", "rank3", "rank4"])
    @pytest.mark.parametrize("layer,ref", _ROWWISE, ids=_ROWWISE_IDS)
    def test_rowwise_matches_jax_nn(self, layer, ref, shape):
        """Values and gradients over the last axis, whatever the rank:
        rank > 2 is computed on the flat (-1, C) view."""
        x = 3.0 * jax.random.normal(rng(1), shape)
        g = jax.random.normal(rng(2), shape)
        y = layer().forward(x)
        assert y.shape == x.shape
        np.testing.assert_allclose(y, ref(x, axis=-1), rtol=0, atol=1e-6)
        got = jax.grad(lambda v: jnp.sum(
            layer().apply({}, {}, v)[0] * g))(x)
        want = jax.grad(lambda v: jnp.sum(ref(v, axis=-1) * g))(x)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)

    @pytest.mark.parametrize("layer,ref", _ROWWISE, ids=_ROWWISE_IDS)
    def test_rowwise_rank2_program_unchanged(self, layer, ref):
        """Rank <= 2 traces the plain jax.nn call, letter for letter:
        the image models' programs and compile-cache entries stay."""
        x = jnp.zeros((256, 1000), jnp.float32)
        got = jax.make_jaxpr(lambda v: layer().apply({}, {}, v)[0])(x)
        want = jax.make_jaxpr(lambda v: ref(v, axis=-1))(x)
        assert str(got) == str(want)

    @pytest.mark.parametrize("layer", [nn.LogSoftMax, nn.SoftMax],
                             ids=_ROWWISE_IDS)
    def test_rowwise_rank3_computes_on_flat_view(self, layer):
        """What lets TimeDistributed's reshapes fold: between a reshape
        to (N*T, C) and a reshape back, no value has the input's rank."""
        x = jnp.zeros((6, 5, 7), jnp.float32)
        eqns = jax.make_jaxpr(
            lambda v: layer().apply({}, {}, v)[0])(x).jaxpr.eqns
        assert len(eqns) >= 3
        assert eqns[0].primitive.name == eqns[-1].primitive.name == "reshape"
        assert eqns[0].outvars[0].aval.shape == (30, 7)
        assert all(v.aval.ndim <= 2
                   for e in eqns[:-1] for v in e.outvars)

    def test_prelu_learnable(self):
        p = nn.PReLU().initialize(0)
        y = p.forward(jnp.array([-2.0, 3.0]))
        np.testing.assert_allclose(y, [-0.5, 3.0])


class TestContainers:
    def test_sequential(self):
        m = nn.Sequential().add(nn.Linear(4, 8)).add(nn.ReLU()).add(nn.Linear(8, 2))
        m.initialize(0)
        y = m.forward(jnp.ones((3, 4)))
        assert y.shape == (3, 2)

    def test_concat_table_parallel_table(self):
        ct = nn.ConcatTable().add(nn.Identity()).add(nn.Identity())
        ct.initialize(0)
        out = ct.forward(jnp.ones((2, 3)))
        assert len(out) == 2
        pt = nn.ParallelTable().add(nn.Linear(3, 4)).add(nn.Identity())
        pt.initialize(0)
        y = pt.forward((jnp.ones((2, 3)), jnp.zeros((2, 5))))
        assert y[0].shape == (2, 4) and y[1].shape == (2, 5)

    def test_concat_dim(self):
        c = nn.Concat(1).add(nn.Linear(3, 4)).add(nn.Linear(3, 6))
        c.initialize(0)
        assert c.forward(jnp.ones((2, 3))).shape == (2, 10)

    def test_caddtable_resnet_shortcut(self):
        block = nn.Sequential() \
            .add(nn.ConcatTable().add(nn.Linear(4, 4)).add(nn.Identity())) \
            .add(nn.CAddTable())
        block.initialize(0)
        assert block.forward(jnp.ones((2, 4))).shape == (2, 4)


class TestShapeOps:
    def test_reshape_view(self):
        assert nn.Reshape((2, 2)).forward(jnp.ones((3, 4))).shape == (3, 2, 2)

    def test_narrow_select(self):
        x = jnp.arange(24.0).reshape(2, 3, 4)
        assert nn.Narrow(1, 1, 2).forward(x).shape == (2, 2, 4)
        assert nn.Select(1, 0).forward(x).shape == (2, 4)

    def test_join_split_roundtrip(self):
        x = jnp.arange(12.0).reshape(2, 2, 3)
        parts = nn.SplitTable(1).forward(x)
        assert len(parts) == 2 and parts[0].shape == (2, 3)
        back = nn.Pack(1).forward(parts)
        np.testing.assert_allclose(back, x)

    def test_lookup_table(self):
        lt = nn.LookupTable(10, 4).initialize(0)
        y = lt.forward(jnp.array([[0, 3], [9, 1]]))
        assert y.shape == (2, 2, 4)

    def test_lrn_runs(self):
        y = nn.SpatialCrossMapLRN(5).forward(jnp.ones((1, 8, 4, 4)))
        assert y.shape == (1, 8, 4, 4)


class TestEagerBackward:
    def test_module_backward_accumulates(self):
        m = nn.Linear(3, 2).initialize(0)
        x = jnp.ones((4, 3))
        y = m.forward(x)
        gi = m.backward(x, jnp.ones_like(y))
        assert gi.shape == x.shape
        _, grads = m.parameters()
        assert float(jnp.sum(jnp.abs(grads["weight"]))) > 0
        m.zero_grad_parameters()
        _, grads = m.parameters()
        assert float(jnp.sum(jnp.abs(grads["weight"]))) == 0.0

    def test_flat_parameters(self):
        m = nn.Sequential().add(nn.Linear(3, 2)).add(nn.Linear(2, 1))
        flat, unravel = m.get_parameters()
        assert flat.shape == (3 * 2 + 2 + 2 * 1 + 1,)
        back = unravel(flat)
        assert back["0"]["weight"].shape == (2, 3)


class TestFullConvolution:
    def test_shape_and_channels(self):
        # output size = (in-1)*stride - 2*pad + kernel + adj
        dc = nn.SpatialFullConvolution(3, 5, 3, 3, stride_w=2, stride_h=2,
                                       pad_w=1, pad_h=1, adj_w=1, adj_h=1)
        dc.initialize(0)
        y = dc.forward(jnp.ones((2, 3, 4, 4)))
        assert y.shape == (2, 5, 8, 8)

    def test_inverts_stride2_conv_shape(self):
        x = jnp.ones((1, 4, 7, 7))
        down = nn.SpatialConvolution(4, 8, 3, 3, 2, 2, 1, 1).initialize(0)
        up = nn.SpatialFullConvolution(8, 4, 3, 3, 2, 2, 1, 1).initialize(1)
        assert up.forward(down.forward(x)).shape == (1, 4, 7, 7)

    def test_matches_manual_1d_case(self):
        # single-channel 1x1 spatial input, kernel 2, stride 2: output is
        # the kernel scaled by the input value
        dc = nn.SpatialFullConvolution(1, 1, 2, 2, 2, 2, with_bias=False)
        dc.initialize(0)
        k = jnp.arange(4.0).reshape(1, 1, 2, 2)
        dc._params["weight"] = k
        y = dc.forward(jnp.full((1, 1, 1, 1), 2.0))
        np.testing.assert_allclose(y, 2.0 * k)


class TestPoolingCeilModeEdge:
    def test_ceil_window_fully_in_padding_dropped(self):
        # kernel 2 stride 3 on size 6: ceil gives out=3 but the 3rd window
        # starts at 6 >= size+pad -> must be dropped (torch semantics)
        pool = nn.SpatialMaxPooling(2, 2, 3, 3, ceil_mode=True)
        y = pool.forward(jnp.ones((1, 1, 6, 6)))
        assert y.shape == (1, 1, 2, 2)
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_ceil_avg_no_nan(self):
        pool = nn.SpatialAveragePooling(2, 2, 3, 3, ceil_mode=True,
                                        count_include_pad=False)
        y = pool.forward(jnp.ones((1, 1, 6, 6)))
        assert bool(jnp.all(jnp.isfinite(y)))
