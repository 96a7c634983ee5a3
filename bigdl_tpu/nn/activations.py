"""Activation layers (reference: ~30 activation files at ``DL/nn/`` —
``ReLU.scala``, ``Tanh.scala``, ``Sigmoid.scala``, ``ELU.scala``,
``PReLU.scala``, ``RReLU.scala``, ``SReLU.scala``, …).

All stateless ones are one jnp expression; XLA fuses them into the
surrounding matmul/conv, which replaces the reference's MKL-DNN fusion pass
(``nn/mkldnn/DnnBase.scala:302-333``) with zero framework code.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module


class _Stateless(Module):
    def _fn(self, x):
        raise NotImplementedError

    def apply(self, params, state, input, *, training=False, rng=None):
        return self._fn(input), state


class ReLU(_Stateless):
    def _fn(self, x):
        return jax.nn.relu(x)


class ReLU6(_Stateless):
    def _fn(self, x):
        return jnp.clip(x, 0.0, 6.0)


class Tanh(_Stateless):
    def _fn(self, x):
        return jnp.tanh(x)


class Sigmoid(_Stateless):
    def _fn(self, x):
        return jax.nn.sigmoid(x)


def _rowwise(fn, x):
    """``fn`` over the last axis, on the flat ``(-1, C)`` view of a
    rank > 2 input: the reshapes ``TimeDistributed`` and
    ``TimeDistributedCriterion`` put on either side then meet and fold,
    forward and backward.  With the row reduction on the 3-D array
    between them, the TPU re-laid PTB's 924 MB of logits out four times
    a step ((N*T, V) <-> (N, T, V) is a physical copy when T is no
    multiple of 8)."""
    if x.ndim <= 2:
        return fn(x, axis=-1)
    return fn(x.reshape(-1, x.shape[-1]), axis=-1).reshape(x.shape)


class SoftMax(_Stateless):
    def _fn(self, x):
        return _rowwise(jax.nn.softmax, x)


class LogSoftMax(_Stateless):
    def _fn(self, x):
        return _rowwise(jax.nn.log_softmax, x)


class SoftPlus(_Stateless):
    def __init__(self, beta: float = 1.0, name=None):
        super().__init__(name)
        self.beta = beta

    def _fn(self, x):
        return jax.nn.softplus(self.beta * x) / self.beta


class SoftSign(_Stateless):
    def _fn(self, x):
        return x / (1.0 + jnp.abs(x))


class ELU(_Stateless):
    def __init__(self, alpha: float = 1.0, inplace: bool = False, name=None):
        super().__init__(name)
        self.alpha = alpha

    def _fn(self, x):
        return jnp.where(x > 0, x, self.alpha * jnp.expm1(x))


class LeakyReLU(_Stateless):
    def __init__(self, negval: float = 0.01, name=None):
        super().__init__(name)
        self.negval = negval

    def _fn(self, x):
        return jnp.where(x >= 0, x, self.negval * x)


class HardTanh(_Stateless):
    def __init__(self, min_value: float = -1.0, max_value: float = 1.0,
                 name=None):
        super().__init__(name)
        self.min_value, self.max_value = min_value, max_value

    def _fn(self, x):
        return jnp.clip(x, self.min_value, self.max_value)


class HardSigmoid(_Stateless):
    def _fn(self, x):
        return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


class GELU(_Stateless):
    """Not in the reference (pre-transformer era) — provided because the
    TPU build treats attention models as first-class."""

    def _fn(self, x):
        return jax.nn.gelu(x)


class SiLU(_Stateless):
    def _fn(self, x):
        return jax.nn.silu(x)


class PReLU(Module):
    """Learnable leaky slope (reference ``PReLU.scala``; nOutputPlane=0
    means one shared slope)."""

    def __init__(self, n_output_plane: int = 0, name=None):
        super().__init__(name)
        self.n_output_plane = n_output_plane

    def init(self, rng):
        n = max(self.n_output_plane, 1)
        return {"weight": jnp.full((n,), 0.25, jnp.float32)}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        w = params["weight"]
        if self.n_output_plane > 0 and input.ndim == 4:
            w = w[None, :, None, None]
        return jnp.where(input >= 0, input, w * input), state


class RReLU(Module):
    """Randomized leaky ReLU (reference ``RReLU.scala``): slope ~
    U(lower, upper) in training, fixed mean slope in eval."""

    def __init__(self, lower: float = 1.0 / 8, upper: float = 1.0 / 3,
                 name=None):
        super().__init__(name)
        self.lower, self.upper = lower, upper

    def apply(self, params, state, input, *, training=False, rng=None):
        if training:
            if rng is None:
                raise ValueError("RReLU in training mode needs an rng")
            a = jax.random.uniform(rng, input.shape, input.dtype,
                                   self.lower, self.upper)
        else:
            a = (self.lower + self.upper) / 2.0
        return jnp.where(input >= 0, input, a * input), state


class SReLU(Module):
    """S-shaped ReLU with 4 learnable params per channel
    (reference ``SReLU.scala``)."""

    def __init__(self, shape: Sequence[int], name=None):
        super().__init__(name)
        self.shape = tuple(shape)

    def init(self, rng):
        return {"t_left": jnp.zeros(self.shape, jnp.float32),
                "a_left": jnp.zeros(self.shape, jnp.float32),
                "t_right": jnp.ones(self.shape, jnp.float32),
                "a_right": jnp.ones(self.shape, jnp.float32)}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        tl, al = params["t_left"], params["a_left"]
        tr, ar = params["t_right"], params["a_right"]
        y = jnp.where(input >= tr, tr + ar * (input - tr),
                      jnp.where(input <= tl, tl + al * (input - tl), input))
        return y, state


class Threshold(_Stateless):
    """(reference ``Threshold.scala``) x if x > th else val."""

    def __init__(self, th: float = 1e-6, v: float = 0.0, name=None):
        super().__init__(name)
        self.th, self.v = th, v

    def _fn(self, x):
        return jnp.where(x > self.th, x, self.v)


class HardShrink(_Stateless):
    """(reference ``HardShrink.scala``) 0 inside [-λ, λ], identity outside."""

    def __init__(self, the_lambda: float = 0.5, name=None):
        super().__init__(name)
        self.the_lambda = the_lambda

    def _fn(self, x):
        return jnp.where(jnp.abs(x) > self.the_lambda, x, 0.0)


class SoftShrink(_Stateless):
    """(reference ``SoftShrink.scala``) shrink magnitudes by λ, 0 inside."""

    def __init__(self, the_lambda: float = 0.5, name=None):
        super().__init__(name)
        self.the_lambda = the_lambda

    def _fn(self, x):
        lam = self.the_lambda
        return jnp.where(x > lam, x - lam, jnp.where(x < -lam, x + lam, 0.0))


class LogSigmoid(_Stateless):
    """(reference ``LogSigmoid.scala``) log(1/(1+e^-x))."""

    def _fn(self, x):
        return jax.nn.log_sigmoid(x)


class SoftMin(_Stateless):
    """(reference ``SoftMin.scala``) softmax of -x over the last dim."""

    def _fn(self, x):
        return jax.nn.softmax(-x, axis=-1)


class TanhShrink(_Stateless):
    """(reference ``TanhShrink.scala``) x - tanh(x)."""

    def _fn(self, x):
        return x - jnp.tanh(x)
