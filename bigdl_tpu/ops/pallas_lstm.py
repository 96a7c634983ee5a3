"""Pallas TPU kernel: fused LSTM cell (opt-in via ``impl``/kernel_impl).

Why this kernel exists: BENCH_r05 puts PTB-LSTM at 0.98 of its HBM
floor — the step is bytes-bound, and the bytes are the gate chain.
XLA lowers ``LSTM.step_hoisted`` (nn/recurrent.py) as a matmul followed
by a chain of entry-visible elementwise ops — the (N, 4H) pre-activation
``z``, four (N, H) gate slices, three sigmoids, two tanhs, and the
cell/hidden updates each materialize an HBM round-trip inside the scan
body.  This kernel computes the whole cell — recurrent matmul (MXU,
f32 accumulation in-register), all four gate nonlinearities, cell
update, and hidden output — in ONE VMEM-resident pass: HBM traffic per
step drops to the operands (zx, h, c, weight panel) plus the three
outputs (h', c', and the f32 ``z`` residual the backward needs).

Backward: ``lstm_cell`` is a ``jax.custom_vjp``.  The forward kernel
emits ``z`` (f32) as its residual; the backward's elementwise part —
gate derivatives, dz, dc_prev — is a second fused kernel, while the two
backward matmuls (dh_prev = dz @ Wh, dWh = hᵀ @ dz) stay on XLA: they
are MXU-bound, XLA schedules them fine, and keeping them outside the
kernel lets the scan transpose accumulate dWh across timesteps the
standard way.

Gating discipline (same as ``ops/pallas_pool.py``): strictly opt-in
behind ``impl="pallas"`` / ``Config.kernel_impl``, with a static
:func:`supported` gate and silent XLA fallback — unsupported shapes
take the reference path with identical semantics.  Bitwise-or-tolerance
parity (forward AND gradient, f32 and bf16) is gated in
``tests/test_pallas_kernels.py``, which runs the real kernel bodies in
interpret mode on CPU.

Constraints this design works around are canonical in
``bigdl_tpu/ops/PALLAS_NOTES.md`` (lane-width rules, per-block element
budget, wrapper-pads-kernel-assumes-alignment).  On-chip bytes/step for
the fused cell are carried measurement debt — the canned-HLO gate in
``tests/test_byte_audit.py`` proves the traffic model, interpret-mode
CPU numbers are correctness-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas_util import (interpret_default as
                                       _interpret_default,
                                       lane_pad as _lane_pad,
                                       sublane_multiple)

# VMEM element budget for the resident recurrent weight panel
# (H_pad x 4*H_pad).  PTB-medium (H=650 -> 768x3072 = 2.36M elements,
# 9.4 MB f32) must pass; the 16 MiB scoped VMEM also holds the
# per-block activations, so gate with headroom below the next power
# step (H=1024 -> 4.2M elements falls back to XLA).  What the chip
# said (PR 21): the 2.36M-element panel compiles for v5e in f32 and
# bf16, forward and backward (tests/test_chip_compile.py), and the f32
# forward + backward ran on a v5e inside the PTB-medium train step with
# losses within 3e-7 of the XLA chain (chip_smoke.py).  Nothing between
# 2.36M and the gate has been compiled or run; an oversize site takes
# the XLA chain through supported(), never an exception handler.
_W_ELEMENT_BUDGET = 3_000_000


def supported(batch: int, hidden: int, dtype) -> bool:
    """Whether the fused cell covers this (N, H, dtype) config.

    Static and conservative (PALLAS_NOTES.md "supported() is the
    opt-in gate"): float32/bfloat16 only, and the lane-padded recurrent
    weight panel must fit the measured VMEM element budget — oversized
    hidden sizes silently keep the XLA chain."""
    import numpy as np
    if np.dtype(dtype) not in (np.dtype(jnp.float32),
                               np.dtype(jnp.bfloat16)):
        return False
    if batch < 1 or hidden < 1:
        return False
    hp = _lane_pad(hidden)
    return hp * 4 * hp <= _W_ELEMENT_BUDGET


def _fwd_kernel(zx_ref, h_ref, c_ref, w_ref, h_out, c_out, z_out, *,
                H, forget_bias):
    # one VMEM-resident pass: recurrent matmul with f32 accumulation
    # in-register, then all four gates + cell/hidden updates in f32
    z = zx_ref[...].astype(jnp.float32) + jnp.dot(
        h_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    z_out[...] = z  # f32 residual for the backward kernel
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H] + forget_bias)
    g = jnp.tanh(z[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(z[:, 3 * H:4 * H])
    c_new = f * c_ref[...].astype(jnp.float32) + i * g
    h_new = o * jnp.tanh(c_new)
    h_out[...] = h_new.astype(h_out.dtype)
    c_out[...] = c_new.astype(c_out.dtype)


def _bwd_kernel(z_ref, c_ref, dh_ref, dc_ref, dz_out, dcp_out, *,
                H, forget_bias):
    # elementwise backward, fused: recompute gates from the f32 z
    # residual, emit dz (f32) and dc_prev; the two matmuls consuming dz
    # run on XLA outside (module docstring)
    z = z_ref[...]
    c = c_ref[...].astype(jnp.float32)
    dh = dh_ref[...].astype(jnp.float32)
    dc = dc_ref[...].astype(jnp.float32)
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H] + forget_bias)
    g = jnp.tanh(z[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(z[:, 3 * H:4 * H])
    c_new = f * c + i * g
    tc = jnp.tanh(c_new)
    dct = dc + dh * o * (1.0 - tc * tc)
    # aligned lane-range stores (no in-kernel concatenate; NOTES.md)
    dz_out[:, :H] = dct * g * i * (1.0 - i)
    dz_out[:, H:2 * H] = dct * c * f * (1.0 - f)
    dz_out[:, 2 * H:3 * H] = dct * i * (1.0 - g * g)
    dz_out[:, 3 * H:4 * H] = dh * tc * o * (1.0 - o)
    dcp_out[...] = (dct * f).astype(dcp_out.dtype)


def _pad2(a, rows, cols):
    r, c = a.shape
    if r == rows and c == cols:
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def _pad_gates(a, rows, H, Hp):
    """Pad (rows0, 4*H) gate-segmented arrays to (rows, 4*Hp): each of
    the i|f|g|o segments is padded independently so kernel-side lane
    slices stay 128-aligned."""
    r = a.shape[0]
    a = a.reshape(r, 4, H)
    a = jnp.pad(a, ((0, rows - r), (0, 0), (0, Hp - H)))
    return a.reshape(rows, 4 * Hp)


def _block_n(n_pad: int) -> int:
    """Batch block: whole batch when small, 128-row blocks otherwise
    (n_pad is a _SUBLANE multiple; 128 divides any larger multiple we
    pick because we round n_pad up to 128 past that point)."""
    return n_pad if n_pad <= 128 else 128


def _pallas_cell(zx, h, c, w_t, *, H, forget_bias, interpret):
    """Aligned-shape fused cell: returns (h', c', z_residual)."""
    N, H4 = zx.shape
    bn = _block_n(N)
    kern = functools.partial(_fwd_kernel, H=H, forget_bias=forget_bias)
    return pl.pallas_call(
        kern,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, H4), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
            pl.BlockSpec((H, H4), lambda n: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
            pl.BlockSpec((bn, H4), lambda n: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H), zx.dtype),
            jax.ShapeDtypeStruct((N, H), zx.dtype),
            jax.ShapeDtypeStruct((N, H4), jnp.float32),
        ],
        interpret=interpret,
    )(zx, h, c, w_t)


def _pallas_cell_bwd(z, c, dh, dc, *, H, forget_bias, interpret):
    """Aligned-shape fused elementwise backward: (dz_f32, dc_prev)."""
    N, H4 = z.shape
    bn = _block_n(N)
    kern = functools.partial(_bwd_kernel, H=H, forget_bias=forget_bias)
    return pl.pallas_call(
        kern,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, H4), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, H4), lambda n: (n, 0)),
            pl.BlockSpec((bn, H), lambda n: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H4), jnp.float32),
            jax.ShapeDtypeStruct((N, H), dc.dtype),
        ],
        interpret=interpret,
    )(z, c, dh, dc)


def _unpad_gates(a, rows, H, Hp):
    """Slice a (*, 4*Hp) gate-segmented array back to (rows, 4*H)."""
    return a.reshape(a.shape[0], 4, Hp)[:rows, :, :H].reshape(rows, 4 * H)


@functools.lru_cache(maxsize=32)
def _cell_fn(H: int, forget_bias: float, interpret: bool):
    """Build (and cache) the custom-vjp fused cell for one static
    config — a fresh custom_vjp per call would defeat jit caching.

    Residual discipline: the per-step residuals are the f32 ``z`` and
    the (padded) ``h``/``c`` — the same order of state XLA saves for the
    scan transpose anyway.  The padded weight panel rides the residuals
    too, but it is a pure function of the loop-invariant weight, so the
    scan partial-eval hoists it out of the stacked extensive outputs
    (verified on the pinned jax: invariant residuals are NOT stacked
    per step)."""

    Hp = _lane_pad(H)

    @jax.custom_vjp
    def cell(zx, h, c, w_t):
        return _fwd(zx, h, c, w_t)[0]

    def _fwd(zx, h, c, w_t):
        N = zx.shape[0]
        # batch padded to the DTYPE's sublane tile minimum — (8, 128)
        # f32, (16, 128) bf16 (PALLAS_NOTES.md)
        sub = sublane_multiple(zx.dtype)
        Np = -(-N // sub) * sub
        if Np > 128:
            Np = -(-Np // 128) * 128  # keep 128-row blocks exact
        zxp = _pad_gates(zx, Np, H, Hp)
        hp = _pad2(h, Np, Hp)
        cp = _pad2(c, Np, Hp)
        wp = _pad_gates(w_t, Hp, H, Hp)
        h_new, c_new, z = _pallas_cell(zxp, hp, cp, wp, H=Hp,
                                       forget_bias=forget_bias,
                                       interpret=interpret)
        out = (h_new[:N, :H], c_new[:N, :H])
        return out, (z, cp, hp, wp)

    def _bwd(res, grads):
        z, cp, hp, wp = res
        dh, dc = grads
        # static facts recovered from the cotangents (residuals must
        # stay arrays-only): N from the unpadded shape, and the zx
        # cotangent dtype — the primal outputs carried zx's dtype, so
        # the incoming cotangents carry it too
        N, zx_dtype = dh.shape[0], dh.dtype
        dhp = _pad2(dh.astype(jnp.float32), z.shape[0], Hp)
        dcp = _pad2(dc.astype(jnp.float32), z.shape[0], Hp)
        dz, dc_prev = _pallas_cell_bwd(z, cp, dhp, dcp, H=Hp,
                                       forget_bias=forget_bias,
                                       interpret=interpret)
        # MXU-bound transposes stay on XLA (module docstring)
        dh_prev = jnp.dot(dz, wp.T.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        dw_t = jnp.dot(hp.T.astype(jnp.float32), dz,
                       preferred_element_type=jnp.float32)
        # cotangent avals must match the primals' (dtype included)
        return (_unpad_gates(dz, N, H, Hp).astype(zx_dtype),
                dh_prev[:N, :H].astype(hp.dtype),
                dc_prev[:N, :H].astype(cp.dtype),
                _unpad_gates(dw_t, H, H, Hp).astype(wp.dtype))

    cell.defvjp(_fwd, _bwd)
    return cell


def lstm_cell(zx, h, c, w_t, *, forget_bias: float = 0.0,
              interpret=None):
    """Fused LSTM cell: ``z = zx + h @ w_t`` then gates/cell/hidden in
    one VMEM pass.

    Args mirror ``nn.recurrent.LSTM.step_hoisted``: ``zx`` (N, 4H) is
    the hoisted input projection + bias, ``h``/``c`` (N, H) the carried
    state, ``w_t`` (H, 4H) the transposed recurrent weight slice.
    Returns ``(h_new, c_new)``; differentiable (custom VJP, fused
    backward).  Caller is responsible for checking :func:`supported`.

    Backward math runs in f32 (gate derivatives from the f32 ``z``
    residual, f32-accumulated matmuls); each cotangent is then cast to
    its primal's dtype, as the custom-vjp contract requires — under
    mixed precision the f32 upcast happens where it always does, in
    the transpose of the loss path's downcast."""
    H = h.shape[-1]
    if interpret is None:
        interpret = _interpret_default()
    cell = _cell_fn(H, float(forget_bias), bool(interpret))
    h_new, c_new = cell(zx, h, c, w_t)
    return h_new, c_new
