"""The Mamba-2 mixer: a selective state-space layer computed as a chunked
scan (the "state space duality" form of Dao & Gu, arXiv:2405.21060).

No reference analog (BigDL predates state-space layers).  The equations
are those of HF ``Mamba2`` / ``GraniteMoeHybridMambaLayer``::

    z, xBC, dt = split(in_proj(u))
    xBC        = silu(conv1d_causal_depthwise(xBC, width d_conv, bias))
    x, B, C    = split(xBC)
    dt         = softplus(dt + dt_bias);   A = -exp(A_log)
    per head:    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (P x S)
                 y_t = S_t C_t + D x_t
    y          = rms_over_d_inner(y * silu(z)) * norm_w       (gate first)
    out        = out_proj(y)

The recurrence is never run step by step here.  Over a chunk of Q steps
it is a masked matrix product (``ssd_chunked_scan``): inside a chunk
``y = ((C B^T) * L) (dt x)`` with ``L[l, m] = exp(sum_{m<j<=l} dt_j A)``,
between chunks a short scan over T / Q carried states.  The products run
on the MXU; state, decays and every product of the scan are f32 at the
highest precision, whatever the layer's compute dtype (a decay is a
product of up to Q factors below one: bf16 loses it).

Departures from HF, each forced: (1) ``held``: the layer can be told
which HEADS it holds of a tensor-parallel layer; ``in_proj`` then has
the held heads' columns of z, x and dt beside the whole B and C
(``n_groups`` groups are replicated, not split), the conv the same
channels, ``out_proj`` the held rows, and the output is the share's
partial sum.  (2) The gated norm's mean of squares runs over the heads
held, summed over ``axis_name`` where the layer is run under one
(``shard_map`` / ``vmap``), so that the shares together normalize over
all of d_inner; on one chip the layer runs without that exchange.
(3) No packed documents: every sequence starts from a zero state.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module
from bigdl_tpu.telemetry.scopes import device_scope

_HI = jax.lax.Precision.HIGHEST
# Mamba-2's initialisation: dt log-uniform in DT_RANGE (floored), A
# uniform in A_RANGE
DT_RANGE, DT_FLOOR, A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


def causal_depthwise_conv1d(x, weight, bias=None):
    """``y[t, c] = bias[c] + sum_k weight[k, c] * x[t - (K - 1) + k, c]``
    with zeros before the sequence.  ``x``: (N, T, C); ``weight``:
    (K, C).  K shifted multiply-adds: at K = 4 a convolution op has
    nothing to win over them."""
    K, T = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(padded[:, k:k + T] * weight[k] for k in range(K))
    return y if bias is None else y + bias


def ssd_chunked_scan(x, dt, A, B, C, chunk: int):
    """The selective state-space recurrence, chunked.

    ``x``: (N, T, H, P) inputs per head; ``dt``: (N, T, H) step sizes
    (after softplus); ``A``: (H,), negative; ``B``, ``C``: (N, T, G, S)
    with G groups of H // G heads sharing one B and C.  Returns ``y``
    (N, T, H, P) with ``y_t = S_t C_t`` (the ``D`` skip is the caller's)
    in f32.  T is padded up to a multiple of ``chunk`` with steps of
    ``dt = 0``, which leave the state as it is."""
    N, T, H, P = x.shape
    G, S = B.shape[2], B.shape[3]
    R = H // G
    f32 = jnp.float32
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                               (a.ndim - 2)) for a in (x, dt, B, C))
    nc = (T + pad) // chunk
    dt = dt.astype(f32).reshape(N, nc, chunk, G, R)
    xdt = x.astype(f32).reshape(N, nc, chunk, G, R, P) * dt[..., None]
    B = B.astype(f32).reshape(N, nc, chunk, G, S)
    C = C.astype(f32).reshape(N, nc, chunk, G, S)
    # log-decay of every step, and its running sum inside a chunk
    a = dt * A.astype(f32).reshape(G, R)
    cs = jnp.cumsum(a, axis=2)                       # (N, c, Q, G, R)
    cs_h = cs.transpose(0, 1, 3, 4, 2)               # (N, c, G, R, Q)

    # inside a chunk: step l reads what step m <= l wrote, decayed
    diff = cs_h[..., :, None] - cs_h[..., None, :]   # [l, m]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    CB = jnp.einsum("nclgs,ncmgs->ncglm", C, B, precision=_HI)
    y = jnp.einsum("ncgrlm,ncmgrp->nclgrp", CB[:, :, :, None] * L, xdt,
                   precision=_HI)

    # what a chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs_h[..., -1:] - cs_h)          # (N, c, G, R, Q)
    added = jnp.einsum("ncmgs,ncmgrp->ncgrps", B,
                       xdt * to_end.transpose(0, 1, 4, 2, 3)[..., None],
                       precision=_HI)
    whole = jnp.exp(cs_h[..., -1])                   # (N, c, G, R)

    def carry(state, inp):
        decay, add = inp
        return state * decay[..., None, None] + add, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((N, G, R, P, S), f32),
        (whole.transpose(1, 0, 2, 3), added.transpose(1, 0, 2, 3, 4, 5)))
    entering = entering.transpose(1, 0, 2, 3, 4, 5)  # (N, c, G, R, P, S)

    # the state a chunk entered with, read by each of its steps
    y = y + jnp.einsum("nclgs,ncgrps->nclgrp", C, entering,
                       precision=_HI) * jnp.exp(cs)[..., None]
    return y.reshape(N, nc * chunk, H, P)[:, :T]


class Mamba2Mixer(Module):
    """The Mamba-2 mixer (module docstring).  ``n_heads`` heads of
    ``head_dim`` with state ``d_state``, ``n_groups`` groups of B and C,
    ``held=(lo, hi)`` the heads this chip holds (default: all).
    Input and output: (N, T, D).  Weights are stored (in, out);
    ``in_proj``'s columns are ``[z | x | B | C | dt]`` as HF orders them."""

    def __init__(self, hidden_size: int, n_heads: int, head_dim: int,
                 d_state: int, *, n_groups: int = 1, d_conv: int = 4,
                 chunk_size: int = 256, held=None, conv_bias: bool = True,
                 eps: float = 1e-5, axis_name: Optional[str] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        lo, hi = held if held is not None else (0, n_heads)
        if not 0 <= lo < hi <= n_heads:
            raise ValueError(f"held heads {held} outside [0, {n_heads}]")
        self.held = (lo, hi)
        self.heads = hi - lo
        if self.heads % n_groups:
            raise ValueError(f"{self.heads} heads held do not split into "
                             f"{n_groups} groups")
        self.hidden_size = hidden_size
        self.head_dim, self.d_state = head_dim, d_state
        self.n_groups, self.d_conv = n_groups, d_conv
        self.chunk_size = chunk_size
        self.conv_bias = conv_bias
        self.eps = eps
        self.axis_name = axis_name
        self.d_inner = self.heads * head_dim         # held
        self.d_bc = n_groups * d_state
        self.splits = (self.d_inner, 2 * self.d_inner,
                       2 * self.d_inner + self.d_bc,
                       2 * self.d_inner + 2 * self.d_bc)

    def init(self, rng):
        """Projections Xavier; the conv as torch's ``Conv1d`` default
        (U(+-1/sqrt(d_conv))); ``A_log``, ``D`` and ``dt_bias`` as
        Mamba-2 sets them: A uniform in ``A_RANGE``, D ones, dt
        log-uniform in ``DT_RANGE`` through the inverse softplus."""
        ks = jax.random.split(rng, 6)
        D, H = self.hidden_size, self.heads
        d_in = self.splits[-1] + H
        conv_dim = self.d_inner + 2 * self.d_bc
        b = 1.0 / math.sqrt(self.d_conv)
        xav = Xavier()
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                                    lo, hi)), DT_FLOOR)
        params = {
            "in_proj": xav.init(ks[0], (D, d_in), D, d_in),
            "conv_w": jax.random.uniform(ks[1], (self.d_conv, conv_dim),
                                         jnp.float32, -b, b),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (H,), jnp.float32, *A_RANGE)),
            "D": jnp.ones((H,), jnp.float32),
            "norm_w": jnp.ones((self.d_inner,), jnp.float32),
            "out_proj": xav.init(ks[5], (self.d_inner, D), self.d_inner, D),
        }
        if self.conv_bias:
            params["conv_b"] = jax.random.uniform(
                ks[2], (conv_dim,), jnp.float32, -b, b)
        return params, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T, _ = input.shape
        H, P, G, S = self.heads, self.head_dim, self.n_groups, self.d_state
        f32 = jnp.float32
        with device_scope("mamba.proj"):
            zxbcdt = input @ params["in_proj"]
            z, xBC, dt = (zxbcdt[..., :self.splits[0]],
                          zxbcdt[..., self.splits[0]:self.splits[3]],
                          zxbcdt[..., self.splits[3]:])
        with device_scope("mamba.conv"):
            xBC = jax.nn.silu(causal_depthwise_conv1d(
                xBC, params["conv_w"], params.get("conv_b")))
        with device_scope("mamba.scan"):
            x = xBC[..., :self.d_inner].reshape(N, T, H, P)
            B = xBC[..., self.d_inner:self.d_inner + self.d_bc]
            C = xBC[..., self.d_inner + self.d_bc:]
            dt = jax.nn.softplus(dt.astype(f32)
                                 + params["dt_bias"].astype(f32))
            A = -jnp.exp(params["A_log"].astype(f32))
            y = ssd_chunked_scan(x, dt, A, B.reshape(N, T, G, S),
                                 C.reshape(N, T, G, S), self.chunk_size)
            y = y + x.astype(f32) * params["D"].astype(f32)[:, None]
            y = y.reshape(N, T, self.d_inner)
        with device_scope("mamba.norm"):
            y = gated_rms_norm(y, z, params["norm_w"], self.eps,
                               self.axis_name).astype(input.dtype)
        with device_scope("mamba.proj"):
            return y @ params["out_proj"], state


def gated_rms_norm(y, z, weight, eps: float,
                   axis_name: Optional[str] = None):
    """``rms(y * silu(z)) * weight`` in f32 (gate first:
    ``norm_before_gate`` false).  Under ``axis_name`` the mean of squares
    runs over the last dim of every participant together."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    ss = jnp.sum(g * g, axis=-1, keepdims=True)
    count = g.shape[-1]
    if axis_name is not None:
        ss = jax.lax.psum(ss, axis_name)
        count = count * jax.lax.psum(1, axis_name)
    return g * jax.lax.rsqrt(ss / count + eps) * weight.astype(f32)
