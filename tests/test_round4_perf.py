"""Round-4 perf work: pallas maxpool backward (interpret mode), phase
maxpool, bf16 stochastic-rounded optimizer state."""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.optim.optim_method import _stochastic_round


def rng(i):
    return jax.random.PRNGKey(i)


class TestPhaseMaxPool:
    CASES = [
        dict(k=3, s=2, p=1, fmt="NCHW", shape=(2, 3, 13, 17)),
        dict(k=3, s=2, p=0, fmt="NHWC", shape=(2, 14, 14, 5)),
        dict(k=3, s=1, p=1, fmt="NHWC", shape=(2, 9, 9, 4)),
        dict(k=2, s=2, p=0, fmt="NCHW", shape=(1, 2, 8, 8)),
        dict(k=5, s=3, p=2, fmt="NHWC", shape=(1, 20, 21, 2), ceil=True),
    ]

    @pytest.mark.parametrize("case", CASES)
    def test_matches_reduce_window(self, case):
        x = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, case["shape"]).astype(np.float32))
        mk = lambda impl: nn.SpatialMaxPooling(
            case["k"], case["k"], case["s"], case["s"], case["p"],
            case["p"], ceil_mode=case.get("ceil", False),
            format=case["fmt"], impl=impl)
        y_ph, _ = mk("phase").apply({}, {}, x)
        y_rw, _ = mk("reduce_window").apply({}, {}, x)
        np.testing.assert_array_equal(np.asarray(y_ph), np.asarray(y_rw))


class TestPallasPoolBwd:
    """First-match parity vs XLA select-and-scatter, via pallas
    interpret mode (runs on CPU; the kernel is compiled for a described
    v5e by tests/test_chip_compile.py)."""

    CASES = [
        ((2, 16, 16, 64), (3, 3), (2, 2), ((0, 1), (0, 1))),
        ((1, 8, 8, 128), (3, 3), (1, 1), ((1, 1), (1, 1))),
        ((1, 12, 12, 8), (2, 2), (2, 2), ((0, 0), (0, 0))),
        ((1, 14, 14, 160), (3, 3), (2, 2), ((1, 1), (1, 1))),  # C pad
    ]

    @pytest.mark.parametrize("shape,kernel,stride,hw_pads", CASES)
    def test_first_match_parity(self, shape, kernel, stride, hw_pads,
                                monkeypatch):
        from bigdl_tpu.ops import pallas_pool
        from jax.experimental import pallas as pl
        import functools

        orig = pl.pallas_call
        monkeypatch.setattr(pallas_pool.pl, "pallas_call",
                            functools.partial(orig, interpret=True))
        # integer values force exact ties → first-match order matters
        x = jnp.asarray(np.random.default_rng(0).integers(
            -4, 5, shape).astype(np.float32))
        dims = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0),) + hw_pads + ((0, 0),)
        w = jnp.cos(jnp.arange(np.prod([
            shape[0],
            (shape[1] + sum(hw_pads[0]) - kernel[0]) // stride[0] + 1,
            (shape[2] + sum(hw_pads[1]) - kernel[1]) // stride[1] + 1,
            shape[3]])))

        def loss_ref(x):
            y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)
            return jnp.sum(y * w.reshape(y.shape))

        def loss_pl(x):
            y = pallas_pool.maxpool_nhwc_with_pallas_bwd(
                x, dims, strides, pads)
            return jnp.sum(y * w.reshape(y.shape))

        g_ref = jax.grad(loss_ref)(x)
        g_pl = jax.grad(loss_pl)(x)
        np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_pl),
                                   atol=1e-5)

    def test_unsupported_falls_back(self):
        from bigdl_tpu.ops.pallas_pool import supported
        assert not supported((1, 13, 13, 4), (3, 3), (2, 2),
                             ((0, 0), (0, 0)))  # H % sh != 0
        assert supported((1, 14, 14, 4), (3, 3), (2, 2), ((1, 1), (1, 1)))


class TestBf16OptimizerState:
    def test_stochastic_round_unbiased(self):
        x = jnp.asarray(np.float32([1.0001, -0.33333, 3.14159e-3]))
        rs = np.stack([
            np.asarray(_stochastic_round(x, jnp.bfloat16, rng(i)).astype(
                jnp.float32)) for i in range(2000)])
        ulp = np.abs(np.asarray(x)) * 0.0078125
        assert (np.abs(rs.mean(0) - np.asarray(x)) < 0.05 * ulp).all()

    def test_sgd_bf16_velocity_trains(self):
        m = optim.SGD(learning_rate=0.5, momentum=0.9,
                      state_dtype=jnp.bfloat16)
        p = {"w": jnp.asarray([2.0, -3.0])}
        s = m.init_state(p)
        assert s["velocity"]["w"].dtype == jnp.bfloat16
        for it in range(50):
            g = {"w": p["w"]}  # grad of 0.5*||w||^2
            p, s = m.update(g, p, s, 0.1, it)
        assert float(jnp.abs(p["w"]).max()) < 0.5  # converges toward 0

    def test_sgd_default_stays_f32(self):
        m = optim.SGD(learning_rate=0.1, momentum=0.9)
        s = m.init_state({"w": jnp.zeros((3,))})
        assert s["velocity"]["w"].dtype == jnp.float32
