"""The gated delta rule (Yang, Kautz, Hatamizadeh: "Gated Delta Networks",
arXiv:2412.06464) and Qwen3-Next's linear-attention layer built on it.

No reference analog (BigDL predates linear attention).  Per value head
the state ``S`` (key_dim x value_dim) starts at 0 and every step::

    S   = exp(g_t) S
    d_t = beta_t (v_t - S^T k_t)          the delta: what S misses of v_t
    S   = S + k_t d_t^T
    o_t = S^T q_t

The recurrence is never run step by step here.  Over a chunk of Q steps
with ``G`` the running sum of ``g`` inside the chunk and ``S0`` the state
the chunk enters with, the deltas solve ONE unit-lower-triangular system
(the UT transform; its solution is the WY form of the chunk's product of
rank-one updates)::

    (I + A) d = beta v - (beta exp(G) k) S0,
    A[t, s] = beta_t exp(G_t - G_s) k_t . k_s        s < t

so ``d = U - W S0`` with ``U``, ``W`` the solutions for ``beta v`` and
``beta exp(G) k``, which do not depend on ``S0``: every chunk solves its
own at once.  One ``lax.scan`` carries the state across chunks (``d``
from ``S0``, then ``S = exp(G_Q) S0 + sum_s exp(G_Q - G_s) k_s d_s^T``),
and each step reads the state it entered with and its chunk's deltas::

    o_t = exp(G_t) S0^T q_t + sum_{s <= t} exp(G_t - G_s) (q_t . k_s) d_s

Every decay is ``exp`` of a difference of running sums taken at or
under the diagonal, so it never exceeds 1.  Everything is f32 at the
highest precision, whatever the layer's compute dtype (a decay is a
product of up to Q factors below one, and the solve divides nothing but
compounds its rows); the backward is autodiff's, the triangular solve's
included.  On a TPU the same equations run through one Pallas kernel
over the chunks (``ops/pallas_delta_rule.py``), the solve left to XLA.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import rms_norm
from bigdl_tpu.nn.mamba import causal_depthwise_conv1d
from bigdl_tpu.nn.module import Module
from bigdl_tpu.telemetry.scopes import device_scope

logger = logging.getLogger("bigdl_tpu.nn")

_HI = jax.lax.Precision.HIGHEST
# the unit length of queries and keys: x / sqrt(sum x^2 + L2_EPS)
L2_EPS = 1e-6
# HF's initialisation of A: uniform in (0, A_MAX), stored as its log
A_MAX = 16.0
# the steps a chunk of the delta rule holds in the layer
CHUNK = 64


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int):
    """The gated delta rule, chunked (module docstring).

    ``q``, ``k``: (N, T, Hk, K) per KEY head, already of unit length and
    scaled as the caller wants; ``v``: (N, T, H, V), value head ``h``
    reading key head ``h // (H / Hk)``; ``g``: (N, T, H), the log-decay
    (<= 0); ``beta``: (N, T, H).  Returns ``o`` (N, T, H, V) in f32.  T
    is padded up to a multiple of ``chunk`` with steps of ``g = 0`` and
    ``beta = 0``, which leave the state as it is.

    One algorithm in the form the arguments allow
    (``ops.pallas_delta_rule.delta_rule_path`` decides, statically, and
    says why): on a TPU, heads of whole 128-lane groups, the Pallas
    kernel, each chunk's work in VMEM; otherwise the vectorised form
    below, one ``lax.scan`` over the chunks."""
    from bigdl_tpu.ops import pallas_delta_rule
    path, why = pallas_delta_rule.delta_rule_path(k.shape, v.shape, v.dtype,
                                                  chunk)
    _log_path(path, why)
    if path == "kernel":
        return pallas_delta_rule.gated_delta_rule(q, k, v, g, beta, chunk)
    r = v.shape[2] // k.shape[2]
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
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
    G = jnp.cumsum(g, axis=-1)                        # (N, H, c, Q)
    on_or_under = jnp.tril(jnp.ones((chunk, chunk), bool))
    under = jnp.tril(on_or_under, -1)
    diff = G[..., :, None] - G[..., None, :]          # [t, s]
    decay = jnp.where(on_or_under, jnp.exp(jnp.where(on_or_under, diff,
                                                     0.0)), 0.0)

    # inside a chunk: the deltas' triangular system, solved for both
    # right-hand sides at once
    kk = jnp.einsum("nhctk,nhcsk->nhcts", k, k, precision=_HI)
    A = jnp.where(under, beta[..., None] * kk * decay, 0.0)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(G))[..., None] * k], axis=-1)
    uw = jax.lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    U, W = uw[..., :V], uw[..., V:]

    # across chunks: one scan carries the state
    k_end = k * jnp.exp(G[..., -1:] - G)[..., None]   # decayed to the end
    whole = jnp.exp(G[..., -1])                       # (N, H, c)

    def carry(S, inp):
        u, w, ke, d = inp
        delta = u - jnp.einsum("nhtk,nhkv->nhtv", w, S, precision=_HI)
        S_next = S * d[..., None, None] + jnp.einsum(
            "nhtk,nhtv->nhkv", ke, delta, precision=_HI)
        return S_next, (S, delta)

    along = lambda a: jnp.moveaxis(a, 2, 0)            # chunks first
    _, (entering, delta) = jax.lax.scan(
        carry, jnp.zeros((N, H, K, V), f32),
        (along(U), along(W), along(k_end), along(whole)))
    entering = jnp.moveaxis(entering, 0, 2)           # (N, H, c, K, V)
    delta = jnp.moveaxis(delta, 0, 2)                 # (N, H, c, Q, V)

    # each step reads the state its chunk entered with, and the deltas
    # of its chunk up to itself
    qk = jnp.einsum("nhctk,nhcsk->nhcts", q, k, precision=_HI) * decay
    o = jnp.einsum("nhctk,nhckv->nhctv", q * jnp.exp(G)[..., None],
                   entering, precision=_HI) \
        + jnp.einsum("nhcts,nhcsv->nhctv", qk, delta, precision=_HI)
    o = jnp.moveaxis(o, 1, 3).reshape(N, nc * chunk, H, V)
    return o[:, :T]


@functools.lru_cache(maxsize=None)
def _log_path(path: str, why: str):
    """Say once a process which form the rule took, and why."""
    logger.info("gated delta rule: %s (%s)", path, why)


def unit_length(x, eps: float = L2_EPS):
    """``x * rsqrt(sum(x^2) + eps)`` over the last dim, in f32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def normed_then_gated(y, z, weight, eps: float):
    """``rms(y) * weight * silu(z)`` over the last dim, in f32: the norm
    FIRST, then the gate (Qwen3-Next's ``RMSNormGated``; Mamba-2's
    ``gated_rms_norm`` gates first)."""
    f32 = jnp.float32
    return rms_norm(y.astype(f32), weight, eps) * jax.nn.silu(z.astype(f32))


class GatedDeltaNet(Module):
    """Qwen3-Next's linear-attention layer (HF ``Qwen3NextGatedDeltaNet``):
    ``key_heads`` key heads of ``key_dim`` and ``value_heads`` value
    heads of ``value_dim`` (a multiple of the key heads: value head ``j``
    reads key head ``j // (value_heads / key_heads)``)::

        q, k, v, z = split(x in_proj_qkvz)     per key head [q | k | v | z]
        b, a       = split(x in_proj_ba)       per key head [b | a]
        q, k, v    = silu(conv1d_causal_depthwise([q; k; v]))   no bias
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   f32
        q, k = unit_length(q) / sqrt(key_dim), unit_length(k)
        o    = gated delta rule (q, k, v, g, beta)  per value head
        out  = (rms(o) * norm_w * silu(z)) out_proj     per value head

    Input and output: (N, T, D).  Weights are stored (in, out).  Every
    head is held: the deployment this layer is built for runs it data
    parallel.  The rule runs in chunks of ``CHUNK`` steps."""

    def __init__(self, hidden_size: int, key_heads: int, value_heads: int,
                 key_dim: int, value_dim: int, *, conv_kernel: int = 4,
                 eps: float = 1e-6, name: Optional[str] = None):
        super().__init__(name)
        if value_heads % key_heads:
            raise ValueError(f"{value_heads} value heads on {key_heads} "
                             "key heads: not a multiple")
        self.hidden_size = hidden_size
        self.key_heads, self.value_heads = key_heads, value_heads
        self.key_dim, self.value_dim = key_dim, value_dim
        self.ratio = value_heads // key_heads
        self.conv_kernel, self.eps = conv_kernel, eps
        # channels a key head's slice of in_proj_qkvz and of in_proj_ba
        self.qkvz_per_head = 2 * key_dim + 2 * self.ratio * value_dim
        self.conv_dim = 2 * key_heads * key_dim + value_heads * value_dim

    def init(self, rng):
        """Projections and the convolution's taps N(0, 0.02) (HF's
        ``initializer_range``); ``A_log`` the log of U(0, 16),
        ``dt_bias`` ones, the norm's weight ones, as HF draws them."""
        ks = jax.random.split(rng, 5)
        D, Hk, Hv = self.hidden_size, self.key_heads, self.value_heads
        normal = lambda k, shape: 0.02 * jax.random.normal(k, shape,
                                                           jnp.float32)
        a = jax.random.uniform(ks[4], (Hv,), jnp.float32, 0.0, A_MAX)
        return {"in_proj_qkvz": normal(ks[0], (D, Hk * self.qkvz_per_head)),
                "in_proj_ba": normal(ks[1], (D, Hk * 2 * self.ratio)),
                "conv_w": normal(ks[2], (self.conv_kernel, self.conv_dim)),
                "dt_bias": jnp.ones((Hv,), jnp.float32),
                "A_log": jnp.log(jnp.maximum(a, jnp.finfo(jnp.float32).tiny)),
                "norm_w": jnp.ones((self.value_dim,), jnp.float32),
                "out_proj": normal(ks[3], (Hv * self.value_dim, D))}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T, _ = input.shape
        Hk, Hv, K, V, r = (self.key_heads, self.value_heads, self.key_dim,
                           self.value_dim, self.ratio)
        f32 = jnp.float32
        with device_scope("gdn.proj"):
            qkvz = (input @ params["in_proj_qkvz"]).reshape(
                N, T, Hk, self.qkvz_per_head)
            ba = (input @ params["in_proj_ba"]).reshape(N, T, Hk, 2 * r)
            q, k = qkvz[..., :K], qkvz[..., K:2 * K]
            v = qkvz[..., 2 * K:2 * K + r * V]
            z = qkvz[..., 2 * K + r * V:].reshape(N, T, Hv, V)
            b, a = ba[..., :r].reshape(N, T, Hv), ba[..., r:].reshape(N, T, Hv)
        with device_scope("gdn.conv"):
            qkv = jax.nn.silu(causal_depthwise_conv1d(
                jnp.concatenate([q.reshape(N, T, Hk * K),
                                 k.reshape(N, T, Hk * K),
                                 v.reshape(N, T, Hv * V)], axis=-1),
                params["conv_w"]))
        with device_scope("gdn.scan"):
            q = qkv[..., :Hk * K].reshape(N, T, Hk, K)
            k = qkv[..., Hk * K:2 * Hk * K].reshape(N, T, Hk, K)
            v = qkv[..., 2 * Hk * K:].reshape(N, T, Hv, V)
            beta = jax.nn.sigmoid(b.astype(f32))
            g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
                a.astype(f32) + params["dt_bias"].astype(f32))
            o = chunked_gated_delta_rule(unit_length(q) / math.sqrt(K),
                                         unit_length(k), v, g, beta, CHUNK)
        with device_scope("gdn.norm"):
            o = normed_then_gated(o, z, params["norm_w"], self.eps)
            o = o.astype(input.dtype).reshape(N, T, Hv * V)
        with device_scope("gdn.proj"):
            return o @ params["out_proj"], state
