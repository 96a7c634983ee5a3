"""Kimi Delta Attention (KDA; Kimi Team: "Kimi Linear", arXiv:2510.26692):
the delta rule whose decay is a vector over the key channels, and the
linear-attention layer of Ling-3.0-flash built on it.

No reference analog.  Per head the state ``S`` (key_dim x value_dim)
starts at 0 and every step::

    S   = Diag(exp(g_t)) S                g_t: a log-decay a KEY CHANNEL
    d_t = beta_t (v_t - S^T k_t)
    S   = S + k_t d_t^T
    o_t = S^T q_t

that is ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t
k_t v_t^T``.  The chunked form is the scalar rule's
(``nn/gated_delta.py``, whose docstring derives it) with every decay a
vector: over a chunk of Q steps with ``G`` the running sum of ``g``
inside the chunk (a vector a step) and ``S0`` the entering state::

    (I + A) d = beta v - (beta exp(G) * k) S0
    A[t, s]   = beta_t sum_c k_tc exp(G_tc - G_sc) k_sc          s < t
    o_t       = (exp(G_t) * q_t)^T S0
                + sum_{s <= t} [sum_c q_tc exp(G_tc - G_sc) k_sc] d_s
    S_next    = Diag(exp(G_Q)) S0 + sum_s (exp(G_Q - G_s) * k_s) d_s^T

**Why sub-chunks.**  A scalar decay comes out of ``k_t . k_s`` as one
factor.  A decay a channel sits INSIDE the sum, and to write the sum as
a matrix product ``exp(G_t - G_s)`` has to be split around a reference
step ``r``: ``(k_t exp(G_t - G_r)) . (k_s exp(G_r - G_s))``.  One half
then grows: at the layer's bound ``g >= -5`` (``LOWER_BOUND``) it
reaches ``e^(5 |r - s|)``, over a chunk of 64 steps ``e^320``, and f32
ends at ``e^88.7``.  So the chunk's ``Q x Q`` products are built from
blocks of ``SUB`` = 16 steps:

- a block ON the diagonal splits at the middle step ``m`` of its
  sub-chunk: ``exp(G_t - G_m)`` and ``exp(G_m - G_s)`` each lie within
  ``e^(+-40)`` (at most 8 steps of at most 5 either way).  The product
  of a pair never overflows, and a cotangent down to 1e-20 times
  ``e^-40`` is still a normal f32 (the TPU flushes subnormals to zero:
  a split at the sub-chunk's edge, ``e^(+-80)``, would lose the small
  gradients there).  The price: a step before the middle of its block
  reads the decays up to the middle step through a factor that cancels,
  so a change there moves it by rounding alone;
- a block UNDER it (rows of sub-chunk ``i``, columns of ``j < i``)
  splits at both sub-chunks' edges: ``exp(G_t - G_{i-})``,
  ``exp(G_{i-} - G_{j+})`` and ``exp(G_{j+} - G_s)`` (``i-``: the step
  before sub-chunk ``i``, ``j+``: the last of ``j``), each a difference
  taken forward in time and so at most 1; a factor that underflows
  stands for a product under 1e-38.

Everything else is ``exp`` of a difference at or under the diagonal, at
most 1.  The rule is therefore exact in f32 for ``g >= -11`` (``8 x 11
= 88``); the layer bounds ``g`` at -5.  All of it f32 at the highest
precision, the backward autodiff's, the triangular solve's included.
It runs in XLA: no kernel takes a vector decay yet (the scalar rule's
Pallas kernel, ``ops/pallas_delta_rule.py``, takes ``g`` (N, T, H)).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import rms_norm
from bigdl_tpu.nn.gated_delta import unit_length
from bigdl_tpu.nn.mamba import causal_depthwise_conv1d
from bigdl_tpu.nn.module import Module
from bigdl_tpu.telemetry.scopes import device_scope

_HI = jax.lax.Precision.HIGHEST
# the steps a chunk of the rule holds, and the steps of one of its blocks
CHUNK = 64
SUB = 16
# Ling-3.0-flash's ``kda_lower_bound``: g = LOWER_BOUND * sigmoid(...)
LOWER_BOUND = -5.0
# fla's initialisation of A (uniform in (1, A_MAX), stored as its log)
# and of the decay's bias (the inverse softplus of a step drawn
# log-uniform in DT_RANGE, at least DT_FLOOR)
A_MAX = 16.0
DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4


@jax.checkpoint
def decayed_products(a, k, G):
    """``M[t, s] = sum_c a_tc exp(G_tc - G_sc) k_sc`` at and under the
    diagonal, 0 above it (module docstring: blocks of ``SUB`` steps).
    ``a``, ``k``, ``G``: (..., Q, K) with ``Q`` a multiple of ``SUB``;
    returns (..., Q, Q).  A checkpoint: its factors, eight arrays of the
    size of ``k``, are made again for the backward and not kept (1.6 GB
    of a KDA layer's backward at 8,192 steps and 32 heads of 128, by a
    v5e compile)."""
    *lead, Q, K = G.shape
    sub = SUB
    n = Q // sub
    blocks = lambda x: x.reshape(*lead, n, sub, K)    # noqa: E731
    a, k, G = blocks(a), blocks(k), blocks(G)
    mid = G[..., sub // 2:sub // 2 + 1, :]            # (..., n, 1, K)
    last = G[..., -1:, :]
    # G at the step before each sub-chunk: 0 before the chunk's first
    before = jnp.concatenate([jnp.zeros_like(last[..., :1, :, :]),
                              last[..., :-1, :, :]], axis=-3)
    on = jnp.einsum("...itk,...isk->...its", a * jnp.exp(G - mid),
                    k * jnp.exp(mid - G), precision=_HI)
    rows = a * jnp.exp(G - before)                    # |.| <= |a|
    cols = k * jnp.exp(last - G)                      # |.| <= |k|
    pairs = [(i, j) for i in range(n) for j in range(i)]
    if pairs:
        i_, j_ = (list(x) for x in zip(*pairs))
        between = jnp.exp(before[..., i_, :, :] - last[..., j_, :, :])
        under = jnp.einsum("...ptk,...psk->...pts",
                           rows[..., i_, :, :] * between,
                           cols[..., j_, :, :], precision=_HI)
    zero = jnp.zeros(on.shape[:-3] + (sub, sub), on.dtype)
    grid = [[on[..., i, :, :] if i == j else
             under[..., pairs.index((i, j)), :, :] if j < i else zero
             for j in range(n)] for i in range(n)]
    M = jnp.concatenate([jnp.concatenate(row, axis=-1) for row in grid],
                        axis=-2)
    return jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), M, 0.0)


def chunked_kda_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The delta rule with a decay a key channel, chunked (module
    docstring).

    ``q``, ``k``: (N, T, H, K), already of unit length and scaled as the
    caller wants; ``v``: (N, T, H, V); ``g``: (N, T, H, K), the
    log-decay (``-11 <= g <= 0``); ``beta``: (N, T, H).  Returns ``o``
    (N, T, H, V) in f32.  T is padded up to a multiple of ``chunk`` with
    steps of ``g = 0`` and ``beta = 0``, which leave the state as it
    is; ``chunk`` is a multiple of ``SUB``."""
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk} steps is no whole number of "
                         f"blocks of {SUB}")
    N, T, H, K = q.shape
    V = v.shape[-1]
    f32 = jnp.float32
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (T + pad) // chunk

    def heads_first(a):             # (N, T, H, ...) -> (N, H, c, Q, ...)
        a = a.astype(f32).reshape((N, nc, chunk, H) + a.shape[3:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (heads_first(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                        # (N, H, c, Q, K)
    under = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    # inside a chunk: the deltas' triangular system, solved for both
    # right-hand sides at once
    A = jnp.where(under, beta[..., None] * decayed_products(k, k, G),
                  0.0)
    rhs = jnp.concatenate([beta[..., None] * v,
                           beta[..., None] * jnp.exp(G) * k], axis=-1)
    uw = jax.lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    U, W = uw[..., :V], uw[..., V:]

    # across chunks: one scan carries the state
    k_end = k * jnp.exp(G[..., -1:, :] - G)           # decayed to the end
    whole = jnp.exp(G[..., -1, :])                    # (N, H, c, K)

    def carry(S, inp):
        u, w, ke, d = inp
        delta = u - jnp.einsum("nhtk,nhkv->nhtv", w, S, precision=_HI)
        S_next = S * d[..., None] + jnp.einsum(
            "nhtk,nhtv->nhkv", ke, delta, precision=_HI)
        return S_next, (S, delta)

    along = lambda a: jnp.moveaxis(a, 2, 0)            # noqa: E731
    _, (entering, delta) = jax.lax.scan(
        carry, jnp.zeros((N, H, K, V), f32),
        (along(U), along(W), along(k_end), along(whole)))
    entering = jnp.moveaxis(entering, 0, 2)           # (N, H, c, K, V)
    delta = jnp.moveaxis(delta, 0, 2)                 # (N, H, c, Q, V)

    # each step reads the state its chunk entered with, and the deltas
    # of its chunk up to itself
    qk = decayed_products(q, k, G)
    o = jnp.einsum("nhctk,nhckv->nhctv", q * jnp.exp(G), entering,
                   precision=_HI) \
        + jnp.einsum("nhcts,nhcsv->nhctv", qk, delta, precision=_HI)
    o = jnp.moveaxis(o, 1, 3).reshape(N, nc * chunk, H, V)
    return o[:, :T]


class KimiDeltaAttention(Module):
    """Ling-3.0-flash's linear-attention layer (KDA with full-rank gates,
    ``no_kda_lora``, and the bounded gate, ``kda_safe_gate``): ``heads``
    heads of ``head_dim`` keys and values each::

        q, k, v, a, z, b = split(x in_proj)     [q | k | v | a | z | b]
        q, k, v = silu(conv1d_causal_depthwise([q; k; v]))    no bias
        g    = LOWER_BOUND sigmoid(exp(A_log) (a + dt_bias))  a channel, f32
        beta = sigmoid(b)                                      a head
        q, k = unit_length(q) / sqrt(head_dim), unit_length(k)   per head
        o    = the rule (q, k, v, g, beta)                     per head
        out  = (rms(o) * norm_w * sigmoid(z)) out_proj         per head

    ``a`` and ``z`` are ``heads * head_dim`` wide (full rank),
    ``dt_bias`` one number a key channel, ``A_log`` one a head.  Input and
    output: (N, T, D).  Weights are stored (in, out).  Every head is
    held: the deployment this layer is built for runs it data parallel.
    The rule runs in chunks of ``CHUNK`` steps, blocks of ``SUB``."""

    def __init__(self, hidden_size: int, heads: int, head_dim: int, *,
                 conv_kernel: int = 4, eps: float = 1e-6,
                 name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.heads, self.head_dim = hidden_size, heads, \
            head_dim
        self.conv_kernel, self.eps = conv_kernel, eps
        self.width = heads * head_dim
        # [q | k | v | a | z] are a head's worth of channels each, b one
        self.in_width = 5 * self.width + heads

    def init(self, rng):
        """Projections and the convolution's taps N(0, 0.02)
        (``initializer_range``); ``A_log`` the log of U(1, 16),
        ``dt_bias`` the inverse softplus of a step log-uniform in
        (0.001, 0.1), the norm's weight ones, as fla draws them."""
        ks = jax.random.split(rng, 5)
        D, C = self.hidden_size, self.width
        normal = lambda k, shape: 0.02 * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[3], (C,), jnp.float32, lo, hi)), DT_FLOOR)
        return {"in_proj": normal(ks[0], (D, self.in_width)),
                "conv_w": normal(ks[1], (self.conv_kernel, 3 * C)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (self.heads,), jnp.float32, 1.0, A_MAX)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_w": jnp.ones((self.head_dim,), jnp.float32),
                "out_proj": normal(ks[2], (C, D))}, {}

    def gate(self, params, a):
        """``g`` (..., heads, head_dim) in f32 of ``a`` (..., width)."""
        f32 = jnp.float32
        a = a.astype(f32) + params["dt_bias"].astype(f32)
        a = a.reshape(a.shape[:-1] + (self.heads, self.head_dim))
        A = jnp.exp(params["A_log"].astype(f32))[:, None]
        return LOWER_BOUND * jax.nn.sigmoid(A * a)

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T, _ = input.shape
        H, K, C = self.heads, self.head_dim, self.width
        f32 = jnp.float32
        with device_scope("kda.project"):
            x = input @ params["in_proj"]
            qkv, a, z, b = (x[..., :3 * C], x[..., 3 * C:4 * C],
                            x[..., 4 * C:5 * C], x[..., 5 * C:])
        with device_scope("kda.conv"):
            qkv = jax.nn.silu(causal_depthwise_conv1d(qkv, params["conv_w"]))
        with device_scope("kda.gate"):
            g = self.gate(params, a)
            beta = jax.nn.sigmoid(b.astype(f32))
        with device_scope("kda.scan"):
            q, k, v = (qkv[..., i * C:(i + 1) * C].reshape(N, T, H, K)
                       for i in range(3))
            o = chunked_kda_rule(unit_length(q) / math.sqrt(K),
                                 unit_length(k), v, g, beta)
        with device_scope("kda.norm"):
            o = rms_norm(o, params["norm_w"], self.eps) * jax.nn.sigmoid(
                z.astype(f32)).reshape(N, T, H, K)
            o = o.astype(input.dtype).reshape(N, T, C)
        with device_scope("kda.project"):
            return o @ params["out_proj"], state
