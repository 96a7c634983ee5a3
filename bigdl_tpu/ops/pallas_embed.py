"""Pallas TPU kernel: fused embedding-bag / COO segment-sum (opt-in).

Why this kernel exists: BENCH_r05 has Wide&Deep at MFU 0.0035 — the
step is pure gather/segment-sum traffic over the wide table
(``nn/sparse.py`` ``coo_spmm``).  XLA lowers that path as
``take`` → ``multiply`` → ``scatter-add``, materializing the
``(nnz, D)`` gathered-and-scaled intermediate in HBM twice (the gather
write and the multiply) before the segment reduction reads it again.
This kernel runs gather + scale + segment-accumulate in ONE pass: the
output accumulator lives in VMEM for the whole kernel, table rows are
double-buffered per-row async DMAs from HBM, and the per-chunk
row/col/value streams ride SMEM block specs — the ``(nnz, D)``
intermediate never exists.  A table narrower than one lane group is
zero-padded to 128 lanes by the wrapper first (one extra ``(V, 128)``
HBM write per call): Mosaic refuses a DMA whose lane extent is not
tile-aligned.

Accumulation is f32 in VMEM regardless of operand dtype; the output is
cast to the same promoted dtype the XLA path produces.  Because the
accumulator is read-modify-write on a resident ref, ROW ORDER DOES NOT
MATTER — unsorted COO, padding entries (row 0, col 0, value 0), empty
rows and duplicate (row, col) pairs all accumulate correctly, so this
kernel accepts exactly what ``coo_spmm`` accepts.

Backward: ``jax.custom_vjp``.  The weight gradient deliberately stays
on XLA's scatter-add — the r5 on-chip ablation measured XLA's scatter
as the best known formulation for the random-update weight grad
(sort+segsum measured worse: PERF.md section 7, "decisions from chip
captures before the ledger") — and
``d_values`` is a row-dot also left to XLA.  The forward is where the
fused win lives.

Gating discipline: opt-in behind ``impl``/``Config.kernel_impl`` with
a static :func:`supported` gate and silent XLA fallback, parity gated
bitwise-or-tolerance (fwd + grad) in ``tests/test_pallas_kernels.py``
under interpret mode on CPU.  Constraint provenance:
``bigdl_tpu/ops/PALLAS_NOTES.md`` (no scatter-add primitive → VMEM
accumulator; SMEM is KBs → per-chunk scalar streams; gather = per-row
DMA).  On-chip bytes/step are carried measurement debt; the canned-HLO
byte gate lives in ``tests/test_byte_audit.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas_util import (interpret_default as
                                       _interpret_default,
                                       lane_pad as _lane_pad)

# nnz entries processed per grid step.  1024 is not a tuning choice:
# XLA lays a 1-D s32/f32 operand out in T(1024) tiles and Mosaic
# verifies each SMEM block against that layout — a (256,) block is
# refused ("XLA layout ({0:T(1024)}) does not match Mosaic layout
# ({0:T(256)})", PALLAS_NOTES.md).  The wrapper pads every stream to a
# multiple of this, so the operands are always whole T(1024) tiles.
# SMEM footprint per step: 3 streams x 1024 x 4 B = 12 KB (SMEM is
# small — never block a whole nnz stream into it).
_CHUNK = 1024

# VMEM element budget for the resident (n_rows, lane-padded D) f32
# output accumulator: the census Wide&Deep wide path (8192 x pad(1)=128
# = 1.05M elements, 4.2 MB) must pass; bigger outputs keep the XLA
# segment-sum.  What the v5e compiler says (libtpu 0.0.34, described
# v5e:2x2, PR 21): a 2,560,000-element accumulator (10.2 MB) compiles,
# a 4,194,304-element one (16.8 MB) is refused with RESOURCE_EXHAUSTED
# "Ran out of memory in memory space vmem" — the bound is the 16 MiB
# scoped-VMEM default in BYTES, not pallas_pool's element-count abort.
# The gate sits at half the largest size seen compiling.
_OUT_ELEMENT_BUDGET = 1_300_000


def supported(nnz: int, n_rows: int, table_shape, dtype) -> bool:
    """Whether the fused bag covers this (nnz, N, table, dtype) config.

    Static and conservative: f32 tables only, feature dim either
    lane-aligned or within one lane group (the wrapper pads it to 128
    lanes), and the VMEM output accumulator within the element budget.

    bf16 tables are refused: XLA tiles a bf16 operand (8,128)(2,1) —
    two rows packed per sublane — and Mosaic cannot slice ONE row out
    of a packed pair for the gather DMA ("Slice shape along dimension 0
    must be aligned to tiling (8), but is 1"; as (V, 1, D):
    "... dimension 1 must be aligned to tiling (2), but is 1"), so a
    bf16 table takes the XLA chain (PALLAS_NOTES.md)."""
    if np.dtype(dtype) != np.dtype(jnp.float32):
        return False
    if nnz < 1 or n_rows < 1:
        return False
    V, D = table_shape
    if not (D % 128 == 0 or D <= 128):
        return False
    return n_rows * _lane_pad(D) <= _OUT_ELEMENT_BUDGET


def _bag_kernel(rows_ref, cols_ref, vals_ref, table_ref, out_ref, buf,
                sem, *, chunk):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        # the accumulator block is VMEM-resident across every grid step
        # (constant index_map); zero it exactly once
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def dma(slot, j):
        # one lane-padded table row HBM -> VMEM: the wrapper pads D to
        # a 128 multiple because Mosaic refuses a DMA slice whose lane
        # extent is not tile-aligned (PALLAS_NOTES.md)
        return pltpu.make_async_copy(
            table_ref.at[cols_ref[j]], buf.at[slot], sem.at[slot])

    dma(0, 0).start()

    def body(j, _):
        slot = jax.lax.rem(j, 2)
        nxt = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < chunk)
        def _():
            dma(nxt, j + 1).start()  # overlap the next gather

        dma(slot, j).wait()
        r = rows_ref[j]
        contrib = vals_ref[j] * buf[slot].astype(jnp.float32)
        # read-modify-write on an unstrided (1, Dp) sub-range — the
        # Mosaic-legal accumulate (no scatter-add primitive)
        out_ref[pl.ds(r, 1), :] = out_ref[pl.ds(r, 1), :] + contrib
        return 0

    jax.lax.fori_loop(0, chunk, body, 0)


@functools.lru_cache(maxsize=32)
def _bag_fn(n_rows: int, interpret: bool):
    """Cached custom-vjp fused bag for one static (n_rows, interpret)."""

    @jax.custom_vjp
    def bag(rows, cols, values, table):
        return _fwd(rows, cols, values, table)[0]

    def _run_kernel(rows, cols, values, table):
        # promoted output dtype from the ORIGINAL operand dtypes (the
        # XLA chain's result dtype); accumulation itself is f32
        out_dtype = jnp.result_type(table.dtype, values.dtype)
        values = values.astype(jnp.float32)
        nnz = rows.shape[0]
        pad = -nnz % _CHUNK
        if pad:
            # padding entries (row 0, col 0, value 0) contribute nothing
            rows = jnp.pad(rows, (0, pad))
            cols = jnp.pad(cols, (0, pad))
            values = jnp.pad(values, (0, pad))
        D = table.shape[1]
        Dp = _lane_pad(D)
        if Dp != D:
            # zero lanes: they gather zeros and are sliced off below
            table = jnp.pad(table, ((0, 0), (0, Dp - D)))
        # (V, 1, Dp): the gathered row is then a slice along an UNTILED
        # leading dim — a (1, Dp) row of a 2-D (V, Dp) operand is only
        # sliceable when XLA happens to pick a (1, 128) HBM tiling
        # (f32, Dp == 128); bf16 and wider tables get (8, 128) tiles
        # and Mosaic refuses the 1-row slice (PALLAS_NOTES.md)
        table = table.reshape(table.shape[0], 1, Dp)
        grid = (rows.shape[0] // _CHUNK,)
        kern = functools.partial(_bag_kernel, chunk=_CHUNK)
        stream = pl.BlockSpec((_CHUNK,), lambda i: (i,),
                              memory_space=pltpu.SMEM)
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                stream, stream, stream,
                pl.BlockSpec(memory_space=pl.ANY),  # table stays HBM
            ],
            out_specs=pl.BlockSpec((n_rows, Dp), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_rows, Dp), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, 1, Dp), table.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret,
        )(rows, cols, values, table)
        if Dp != D:
            out = out[:, :D]
        return out.astype(out_dtype)

    def _fwd(rows, cols, values, table):
        out = _run_kernel(rows, cols, values, table)
        return out, (rows, cols, values, table)

    def _bwd(res, g):
        rows, cols, values, table = res
        gf = g.astype(jnp.float32)
        g_rows = jnp.take(gf, rows, axis=0)  # (nnz, D)
        # weight grad: XLA's scatter-add — measured best-known for the
        # random-update pattern (module docstring)
        d_table = jnp.zeros(table.shape, jnp.float32).at[cols].add(
            values.astype(jnp.float32)[:, None] * g_rows)
        d_values = jnp.sum(
            g_rows * jnp.take(table, cols, axis=0).astype(jnp.float32),
            axis=1)
        int0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
        return (int0(rows), int0(cols), d_values.astype(values.dtype),
                d_table.astype(table.dtype))

    bag.defvjp(_fwd, _bwd)
    return bag


def embedding_bag_coo(rows, cols, values, table, n_rows: int, *,
                      interpret=None):
    """Fused COO embedding-bag: ``out[r] += values[k] * table[cols[k]]``
    for every non-zero ``k`` with ``rows[k] == r``, in one pass.

    Drop-in for the ``coo_spmm`` gather→scale→segment_sum chain
    (identical semantics for unsorted rows, duplicates, padding zeros
    and empty segments).  Differentiable; the weight grad keeps XLA's
    scatter-add.  Caller is responsible for checking :func:`supported`.
    """
    if interpret is None:
        interpret = _interpret_default()
    fn = _bag_fn(int(n_rows), bool(interpret))
    return fn(rows.astype(jnp.int32), cols.astype(jnp.int32), values,
              table)
