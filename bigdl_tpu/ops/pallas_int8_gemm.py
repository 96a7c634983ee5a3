"""Pallas TPU kernel: int8 mixed-precision GEMM (quantized inference).

Reference: BigQuant's JNI int8 GEMM (``DL/nn/quantized/Linear.scala:
79-90`` — int8 weights per output channel, activations quantized on the
fly, int32 accumulate, dequantize).  Until this kernel, the TPU port
only SIMULATED that backend: ``nn/quantized.py`` issued an ordinary XLA
``dot_general`` on int8 operands, so ``deploy(quantize=True)`` saved
weight memory but bought zero serving speed.  Small-batch inference is
weight-panel-bytes-bound — the (K, O) panel is re-read from HBM every
dispatch while the activation block is tiny — so an int8-resident panel
is a 4x (vs f32) / 2x (vs bf16) cut in the dominant traffic term.  This
kernel keeps the int8 panel VMEM-resident across the row-block grid and
fuses the whole quantized epilogue (dequantize by the per-output-channel
f32 scale, bias add) in-register.

Two per-layer modes share ONE math definition (:func:`_matmul_math`,
used verbatim by the kernel body and the XLA fallback so the two cannot
drift):

- ``weight_only``: f32/bf16 activations against the int8 panel upcast
  in-register, f32 MXU accumulation (``preferred_element_type=f32``) —
  no activation quantization error, the serving default;
- ``dynamic``: activations quantized on the fly per-tensor
  (:func:`dyn_quantize`, BigQuant's runtime scheme), int8 x int8 MXU
  issue with int32 accumulation (``preferred_element_type=int32`` —
  Mosaic requires an int accumulator for int operands), dequantize by
  the combined ``x_scale * w_scale_o``.

Gating discipline (PR-8, same as ``ops/pallas_lstm.py``): strictly
opt-in behind ``impl="pallas"`` / ``Config.kernel_impl``, static
:func:`supported` gate, silent XLA fallback.  The fallback here is
BITWISE-identical, not merely tolerance-close: ``supported()`` requires
K and O already 128-lane-aligned, so the wrapper never pads the
contraction or output dims (padding K would perturb f32 accumulation
order); only batch rows are padded, and the fallback replicates the
kernel's row grid exactly (:func:`_pad_plan` + one dot per block via
``lax.map``) because the host gemm's reduction order depends on the M
it is handed.  Forward-only by design — quantized
modules are inference twins (no ``custom_vjp``), which is what keeps
the builder a plain ``lru_cache``.

Constraints are canonical in ``bigdl_tpu/ops/PALLAS_NOTES.md`` (int8
(32, 128) tile minimum, accumulate dtype rules, VMEM budget
provenance).  All gating below is host code — static ``supported()``
decisions at trace time, never data-dependent dispatch (graftlint
catalog note).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas_util import (interpret_default as
                                       _interpret_default,
                                       sublane_multiple)

# VMEM element budget for the resident int8 weight panel (K x O int8 =
# 1 byte/element, vs 4 for pallas_lstm's f32 panel).  6M elements = 6 MB
# of the 16 MiB scoped VMEM, leaving room for the <=128-row activation
# and f32 output blocks (128 x (K + O) elements at the gated sizes).
# What the chip said (PR 21): a 2048x2048 panel (4.19M elements)
# compiles for v5e in both modes at 256 rows
# (tests/test_chip_compile.py), and weight_only ran on a v5e at row
# buckets 1..64 inside the serving executables, within the documented
# int8 bar of the f32 twin (chip_smoke.py).  Nothing between 4.19M and
# the gate has been compiled or run; an oversize panel takes the
# bitwise-identical XLA path through supported().  Notes:
# ops/PALLAS_NOTES.md §int8.
_W_ELEMENT_BUDGET_INT8 = 6_000_000

MODES = ("weight_only", "dynamic")

# int8 vreg tile minimum is (32, 128) (PALLAS_NOTES.md): dynamic-mode
# activation blocks are int8, so their row padding uses this sublane
# multiple instead of the f32/bf16 ones pallas_util knows about
_INT8_SUBLANE = 32


def _sublane(dtype) -> int:
    if np.dtype(dtype) == np.dtype(jnp.int8):
        return _INT8_SUBLANE
    return sublane_multiple(dtype)


def supported(batch: int, in_features: int, out_features: int, x_dtype,
              mode: str = "weight_only") -> bool:
    """Whether the fused GEMM covers this (N, K, O, dtype, mode) config.

    Static and conservative (PALLAS_NOTES.md "supported() is the opt-in
    gate"), decided on the host at trace time.  K and O must ALREADY be
    128-lane multiples — the wrapper refuses to pad the contraction or
    output dims so the pallas path stays bitwise-identical to the XLA
    fallback (module docstring); odd shapes silently keep the XLA
    quantized chain.  f32/bf16 activations only, and the int8 weight
    panel must fit the VMEM element budget."""
    if mode not in MODES:
        return False
    if np.dtype(x_dtype) not in (np.dtype(jnp.float32),
                                 np.dtype(jnp.bfloat16)):
        return False
    if batch < 1 or in_features < 1 or out_features < 1:
        return False
    if in_features % 128 != 0 or out_features % 128 != 0:
        return False
    return in_features * out_features <= _W_ELEMENT_BUDGET_INT8


def dyn_quantize(x: jnp.ndarray):
    """Per-tensor dynamic symmetric int8 activation quantization
    (traced; the scale is a runtime value, exactly BigQuant's on-the-fly
    scheme).  Returns ``(int8 values, scale)``; the scale keeps ``x``'s
    dtype-promotion behaviour so downstream ``x_scale * w_scale``
    lands in f32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _matmul_math(xin, wq_t, scale_row, bias_row, mode):
    """THE quantized GEMM math — single definition site shared by the
    kernel body (on block refs) and the XLA fallback (on full arrays),
    so the two paths cannot drift.  ``xin`` is f32/bf16 (weight_only)
    or already-quantized int8 (dynamic); ``wq_t`` is the (K, O) int8
    panel; ``scale_row``/``bias_row`` are (1, O) f32.  Returns f32."""
    if mode == "weight_only":
        acc = jnp.dot(xin.astype(jnp.float32),
                      wq_t.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    else:  # dynamic: int8 x int8 -> int32 accumulate (Mosaic rule)
        acc = jnp.dot(xin, wq_t,
                      preferred_element_type=jnp.int32
                      ).astype(jnp.float32)
    y = acc * scale_row
    if bias_row is not None:
        y = y + bias_row
    return y


def _kernel_bias(x_ref, w_ref, s_ref, b_ref, o_ref, *, mode):
    o_ref[...] = _matmul_math(x_ref[...], w_ref[...], s_ref[...],
                              b_ref[...], mode)


def _kernel_nobias(x_ref, w_ref, s_ref, o_ref, *, mode):
    o_ref[...] = _matmul_math(x_ref[...], w_ref[...], s_ref[...],
                              None, mode)


def _auto_block(n_pad: int) -> int:
    """Row block: whole batch when small, 128-row blocks otherwise
    (n_pad is already a sublane multiple; past 128 it is rounded to a
    128 multiple so the grid divides exactly)."""
    return n_pad if n_pad <= 128 else 128


def _pad_plan(N: int, dtype, block_rows: int):
    """(n_pad, bn) row padding/blocking for a batch — ONE definition
    shared by the kernel wrapper and the XLA fallback, because the
    fallback must replicate the kernel's grid exactly: the host gemm's
    f32 reduction order depends on the M it is handed (XLA CPU blocks
    a 304-row gemm differently from a 128-row one under intra-op
    threading), so bitwise parity requires identical per-block dots,
    not merely identical math."""
    sub = _sublane(dtype)
    if block_rows > 0:
        bn = -(-block_rows // sub) * sub
        n_pad = -(-N // bn) * bn
    else:
        n_pad = -(-N // sub) * sub
        if n_pad > 128:
            n_pad = -(-n_pad // 128) * 128
        bn = _auto_block(n_pad)
    return n_pad, bn


@functools.lru_cache(maxsize=64)
def _gemm_fn(K: int, O: int, mode: str, has_bias: bool,
             block_rows: int, interpret: bool):
    """Build (and cache) the padded-shape pallas caller for one static
    (K, O, mode, bias, block, interpret) config.  Forward-only — no
    custom_vjp — so the cache is a plain memo keeping wrapper identity
    stable across trace sites."""

    def run(xin, wq_t, scale_row, bias_row):
        N = xin.shape[0]
        # batch rows pad to the INPUT dtype's sublane tile minimum —
        # (8,128) f32 / (16,128) bf16 / (32,128) int8 (PALLAS_NOTES.md);
        # an explicit block_rows is itself rounded to
        # that multiple and the batch pads up to a whole block count
        n_pad, bn = _pad_plan(N, xin.dtype, block_rows)
        if n_pad != N:
            xin = jnp.pad(xin, ((0, n_pad - N), (0, 0)))
        ins = [xin, wq_t, scale_row]
        in_specs = [
            pl.BlockSpec((bn, K), lambda n: (n, 0)),
            pl.BlockSpec((K, O), lambda n: (0, 0)),
            pl.BlockSpec((1, O), lambda n: (0, 0)),
        ]
        if has_bias:
            ins.append(bias_row)
            in_specs.append(pl.BlockSpec((1, O), lambda n: (0, 0)))
            kern = functools.partial(_kernel_bias, mode=mode)
        else:
            kern = functools.partial(_kernel_nobias, mode=mode)
        out = pl.pallas_call(
            kern,
            grid=(n_pad // bn,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bn, O), lambda n: (n, 0)),
            out_shape=jax.ShapeDtypeStruct((n_pad, O), jnp.float32),
            interpret=interpret,
        )(*ins)
        return out[:N]

    return run


def int8_matmul(x, wq, wscale, bias=None, *, mode: str = "weight_only",
                impl=None, block_rows=None, interpret=None):
    """Quantized ``x @ wq.T (+ bias)`` — the kernel-backed inference
    primitive behind ``nn/quantized.py``.

    Args:
      x: (N, K) f32/bf16 activations.
      wq: (O, K) int8 weights (symmetric per-output-channel).
      wscale: (O,) or (O, 1) f32 per-output-channel scales.
      bias: optional (O,) f32.
      mode: ``"weight_only"`` (f32-accumulated, no activation error) or
        ``"dynamic"`` (on-the-fly int8 activations, int32 accumulate).
      impl: per-call kernel_impl override; None defers to
        ``resolve_kernel_impl`` (Engine / Config).
      block_rows: row-block size; None defers to
        ``Config.int8_block_rows`` (0 = auto: <=128 whole-batch).
      interpret: pallas interpret override; None = auto (True off-TPU).

    Returns f32 (N, O).  Unsupported shapes/modes silently take the
    BITWISE-identical XLA fallback (module docstring).
    """
    if mode not in MODES:
        raise ValueError(
            f"int8 activation mode must be one of {MODES}, got {mode!r}")
    from bigdl_tpu.ops import resolve_kernel_impl
    eff = resolve_kernel_impl(impl)
    if block_rows is None:
        from bigdl_tpu.utils.config import get_config
        block_rows = get_config().int8_block_rows
    N, K = x.shape
    O = wq.shape[0]
    wscale_f = wscale.reshape(-1).astype(jnp.float32)
    if mode == "dynamic":
        xin, xs = dyn_quantize(x)
        scale_row = (xs * wscale_f).astype(jnp.float32).reshape(1, O)
    else:
        xin = x
        scale_row = wscale_f.reshape(1, O)
    bias_row = None if bias is None \
        else bias.astype(jnp.float32).reshape(1, O)
    wq_t = wq.T
    if eff != "pallas" or not supported(N, K, O, x.dtype, mode):
        # canonical XLA path.  For shapes the kernel covers, replicate
        # the kernel's EXACT row grid (_pad_plan + one dot per block
        # via lax.map): the host gemm's f32 reduction order depends on
        # the M it is handed, so a single big gemm over the whole
        # padded batch is NOT bitwise-equal to the kernel's per-block
        # dots once the grid has >1 block (and an unpadded N=1 dot
        # lowers as a gemv with yet another order).  lax.map serializes
        # the blocks — the documented price of the bitwise-fallback
        # contract on multi-block batches; each block is still a full
        # (bn, K) x (K, O) gemm.
        if supported(N, K, O, x.dtype, mode):
            n_pad, bn = _pad_plan(N, xin.dtype, int(block_rows))
            if n_pad != N:
                xin = jnp.pad(xin, ((0, n_pad - N), (0, 0)))
            if n_pad == bn:
                return _matmul_math(xin, wq_t, scale_row, bias_row,
                                    mode)[:N]
            yb = jax.lax.map(
                lambda xb: _matmul_math(xb, wq_t, scale_row, bias_row,
                                        mode),
                xin.reshape(n_pad // bn, bn, K))
            return yb.reshape(n_pad, O)[:N]
        return _matmul_math(xin, wq_t, scale_row, bias_row, mode)
    if interpret is None:
        interpret = _interpret_default()
    fn = _gemm_fn(K, O, mode, bias is not None, int(block_rows),
                  bool(interpret))
    return fn(xin, wq_t, scale_row, bias_row)
