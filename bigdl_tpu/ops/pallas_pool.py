"""Pallas TPU kernel for max-pool backward (opt-in; see verdict below).

Why this kernel exists: XLA lowers the gradient of
``lax.reduce_window(max)`` to ``select-and-scatter``, which on TPU runs
far below HBM bandwidth.  Measured on v5e at batch 256 (Inception-v1,
NHWC): the full training step takes 55.1 ms with select-and-scatter
backward vs 46.5 ms with an equal-traffic elementwise backward — ~8.6 ms
of pure lowering waste per step (the reference hits the same op count in
its MKL maxpool backward, ``DL/nn/SpatialMaxPooling.scala``
updateGradInput).

The kernel computes the same first-match semantics as
select-and-scatter / the reference's argmax backward: each output
window routes its gradient to the FIRST position (row-major scan order)
equal to the window max.

Measured verdict (r4): the kernel itself is correct and VMEM-resident,
but pallas only accepts default (row-major) layouts while XLA lays the
surrounding activations out batch-minor (``{0,3,2,1}``) — so XLA
inserts full-tensor layout copies around every call, costing ~3× more
than the select-and-scatter waste the kernel removes (Inception-v1
bytes/step 37.3→80.4 GB).  Until pallas grows input-layout control,
``SpatialMaxPooling`` keeps ``reduce_window`` as its default and this
kernel is opt-in (``impl="pallas_bwd"``), retained as the reference
first-match implementation and for layout-friendly call-sites.

r5 addendum, refreshed round-10 (the fused-kernel PR): the PINNED
toolchain — whichever jax/jaxlib the bench ``toolchain`` stamp names
for a given capture; cross-version claims were the r4→r5 trap — still
has NO pallas input-layout control, so the copy penalty around
batch-minor conv activations stands, and its Mosaic rejects the
large-spatial blocks an earlier toolchain accepted (see
:func:`supported`, which gates on the measured 410K per-block ELEMENT
budget and falls back).  Re-verify BOTH facts per toolchain bump; the
2-D-activation kernels (``pallas_lstm.py``, ``pallas_embed.py``) are
unaffected by the layout issue because their operands use default
row-major layouts.

The Mosaic lowering constraints that shape this design (no
scatter-add, lane-width/strided-access rules, the element budget,
f32-compare masks) are canonical in ``bigdl_tpu/ops/PALLAS_NOTES.md``
— kept there so every ops/pallas_* kernel cites ONE constraints doc
instead of restating and drifting.  Specific to this kernel: all
strided window access is factored out as free XLA reshapes
(``(N, H, W, C) -> (N, H/sh, sh, W/sw, sw*C)`` regroups contiguous
memory, so a window offset ``d = q*s + r`` becomes an UNSTRIDED slice
``[i+q, r]`` with the ``r``-selection a 128-aligned lane-range slice),
and gradient accumulation is read-modify-write on the output ref over
those unstrided sub-ranges.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bwd_kernel(x_ref, y_ref, g_ref, gi_ref, taken_ref, *, kh, kw, sh, sw,
                ph, pw, GH, GW, OH, OW, C):
    gi_ref[0] = jnp.zeros(gi_ref.shape[1:], gi_ref.dtype)
    # "window already matched" mask lives in a VMEM scratch ref so it
    # can be updated on the same sub-ranges the windows touch (a
    # functional value would need pads, which Mosaic cannot lower for
    # bf16/i1 vectors here).  Float 0/1 rather than bool: reused i1
    # vectors force failing relayouts.
    taken_ref[...] = jnp.zeros(taken_ref.shape, taken_ref.dtype)
    for dh in range(kh):
        # offset relative to the unpadded input: divmod handles the
        # negative (lo-padding) side correctly
        qh, rh = divmod(dh - ph, sh)
        i0, i1 = max(0, -qh), min(OH, GH - qh)
        if i0 >= i1:
            continue
        for dw in range(kw):
            qw, rw = divmod(dw - pw, sw)
            j0, j1 = max(0, -qw), min(OW, GW - qw)
            if j0 >= j1:
                continue
            cand = x_ref[0, i0 + qh:i1 + qh, rh:rh + 1,
                         j0 + qw:j1 + qw, rw * C:(rw + 1) * C]
            # compared in f32: the VPU has no bf16 vector compare, and
            # i1 masks born from packed-bf16 compares force Mosaic
            # relayouts that fail to lower.  Single boolean use, float
            # thereafter.
            hitf = jnp.where(
                cand.astype(jnp.float32) ==
                y_ref[0, i0:i1, :, j0:j1, :].astype(jnp.float32),
                jnp.float32(1.0), jnp.float32(0.0)).astype(x_ref.dtype)
            tsub = taken_ref[i0:i1, :, j0:j1, :]
            fresh = hitf * (jnp.ones((), tsub.dtype) - tsub)
            contrib = g_ref[0, i0:i1, :, j0:j1, :] * fresh.astype(
                gi_ref.dtype)
            taken_ref[i0:i1, :, j0:j1, :] = jnp.maximum(tsub, hitf)
            cur = gi_ref[0, i0 + qh:i1 + qh, rh:rh + 1,
                         j0 + qw:j1 + qw, rw * C:(rw + 1) * C]
            gi_ref[0, i0 + qh:i1 + qh, rh:rh + 1,
                   j0 + qw:j1 + qw, rw * C:(rw + 1) * C] = cur + contrib


def _lane_pad(C: int) -> int:
    """Channels after vreg lane alignment (shared with the kernel's
    padding rule in :func:`maxpool_bwd_nhwc`)."""
    return C if C <= 128 else -(-C // 128) * 128


def supported(x_shape, kernel, stride, pads):
    """Whether the pallas backward covers this pooling config.

    Besides the structural conditions, a per-block ELEMENT budget gate.
    Its history: an earlier toolchain's Mosaic aborted compilation
    (compile-helper exit 1, no diagnostic) on 802,816-element blocks in
    both f32 and bf16, and the gate sat at 410,000.  Asked again for a
    described v5e:2x2 under jax 0.9.0 / libtpu 0.0.34 (PR 21,
    ``tests/test_chip_compile.py``) that abort is gone: blocks of
    401,408 (56²×128), 802,816 (112²×64, 56²×256, 28²×1024) and
    1,605,632 elements (112²×128, 56²×512) compile in f32 and bf16;
    at 3,211,264 elements 112²×256 compiles but 224²×64 is refused —
    ``RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem`` —
    so past that point the answer depends on shape, not count.  Gate
    just above the largest count that compiled in every shape tried;
    bigger sites take the documented reduce_window fallback instead of
    a compile error.  Compile-only evidence: no block size has RUN on
    this toolchain's chip (the kernel is opt-in, off the main path).
    Re-ask per toolchain bump (canonical note: ops/PALLAS_NOTES.md)."""
    _, H, W, C = x_shape
    (kh, kw), (sh, sw) = kernel, stride
    if not (H % sh == 0 and W % sw == 0 and kh >= sh and kw >= sw):
        return False
    return H * W * _lane_pad(C) <= 1_700_000


def maxpool_bwd_nhwc(x, y, g, kernel, stride, pads):
    """First-match max-pool input-gradient, NHWC.

    ``pads`` is ((ph_lo, ph_hi), (pw_lo, pw_hi)) as given to
    reduce_window; only the lo values matter for indexing (hi padding
    never matches a window max)."""
    N, H, W, C = x.shape
    _, OH, OW, _ = y.shape
    (kh, kw), (sh, sw) = kernel, stride
    (ph, _), (pw, _) = pads

    # lane alignment: pad channels to a 128 multiple so every lane
    # slice in the kernel is vreg-aligned (only the branchy concat
    # widths 192/480/528/832 pay this, and those tensors are small)
    C_eff = _lane_pad(C)
    if C_eff != C:
        x = jnp.pad(x, ((0, 0),) * 3 + ((0, C_eff - C),),
                    constant_values=-jnp.inf)
        y = jnp.pad(y, ((0, 0),) * 3 + ((0, C_eff - C),),
                    constant_values=-jnp.inf)
        g = jnp.pad(g, ((0, 0),) * 3 + ((0, C_eff - C),))

    GH, GW = H // sh, W // sw
    x5 = x.reshape(N, GH, sh, GW, sw * C_eff)    # free: contiguous regroup
    y5 = y.reshape(N, OH, 1, OW, C_eff)
    g5 = g.reshape(N, OH, 1, OW, C_eff)

    kern = functools.partial(_bwd_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                             ph=ph, pw=pw, GH=GH, GW=GW, OH=OH, OW=OW,
                             C=C_eff)
    gi5 = pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, GH, sh, GW, sw * C_eff),
                         lambda n: (n, 0, 0, 0, 0)),
            pl.BlockSpec((1, OH, 1, OW, C_eff), lambda n: (n, 0, 0, 0, 0)),
            pl.BlockSpec((1, OH, 1, OW, C_eff), lambda n: (n, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, GH, sh, GW, sw * C_eff),
                               lambda n: (n, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x5.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((OH, 1, OW, C_eff), x.dtype)],
    )(x5, y5, g5)
    gi = gi5.reshape(N, H, W, C_eff)
    return gi[..., :C] if C_eff != C else gi


def maxpool_nhwc_with_pallas_bwd(x, dims, strides, pads):
    """reduce_window(max) forward + pallas first-match backward.

    Drop-in for the NHWC max-pool forward; the fwd op is XLA's own
    (near bandwidth), only the pathological select-and-scatter backward
    is replaced.  Falls back to plain reduce_window (select-and-scatter
    backward) when :func:`supported` says no."""
    kernel = (dims[1], dims[2])
    stride = (strides[1], strides[2])
    hw_pads = (pads[1], pads[2])

    if not supported(x.shape, kernel, stride, hw_pads):
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)

    @jax.custom_vjp
    def pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)

    def fwd(x):
        y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)
        return y, (x, y)

    def bwd(res, g):
        x, y = res
        return (maxpool_bwd_nhwc(x, y, g, kernel, stride, hw_pads),)

    pool.defvjp(fwd, bwd)
    return pool(x)
