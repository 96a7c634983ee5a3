"""Pallas TPU kernel: the chunked gated delta rule, forward and backward.

Why this kernel exists: ``nn.gated_delta.chunked_gated_delta_rule``'s
vectorised XLA form writes every f32 intermediate of the chunked rule to
HBM and reads it back (the chunks' right-hand sides and solutions, the
entering states and their transpose, ``q`` and ``k`` repeated to the
value heads, the decayed keys, the deltas, the output), and walks a
``lax.scan`` of one step a chunk three times over: forward, again under
the layer's checkpoint, and backward.  At Qwen3-Next's 8,192 tokens and
32 value heads that is some 10 GB a layer where the rule needs 1.6, and
36 % of the training step at 3.8 % of its roofline (PERF.md).
Here a chunk's products, decays and deltas live in VMEM only, and the
``(K, V)`` state of each value head is carried in VMEM scratch across a
sequential chunk axis of the grid.

**The split** (module docstring of ``nn/gated_delta.py`` for the
equations).  XLA keeps the per-token scalars and the chunk's triangular
system: ``G`` the running sum of ``g`` inside a chunk, and ``T = (I +
A)^{-1}`` by ``triangular_solve`` against the identity (substitution,
which is stable; the doubling product form is not: PALLAS_NOTES.md),
with autodiff's backward.  The kernel runs, per chunk and value head::

    U | W = (T diag(beta)) [v | e^G k]
    delta = U - W S
    o     = (e^G q) S + ((q k^T) o D) delta        D[t, s] = e^{G_t - G_s}, s <= t
    S    <- e^{G_Q} S + (e^{G_Q - G} k)^T delta

ONE function (``_chunk``) states that arithmetic for the value heads of
one key head; the forward kernel calls it, the backward kernel calls its
``jax.vjp``, traced inside the kernel.  The forward writes each chunk's
ENTERING state as the backward's residual (``f32[N, Hv, c, K, V]``,
once); the backward walks the chunks in reverse with ``dS`` in VMEM
scratch and recomputes a chunk's ``U``, ``W`` and ``delta`` from the
saved state.

**Layouts read as they lie.**  The grid is (batch, key head, chunk),
the chunk innermost and ``"arbitrary"``.  ``q`` and ``k`` are read per
KEY head from ``(N, T, Hk K)`` (the key head's lane block), the value
heads of that key head from ``(N, T, Hv V)`` (they are neighbours), and
``o`` is written as ``(N, T, Hv V)``: no repeat of the keys, no
heads-first copy.  ``dq`` and ``dk`` come out per key head,
summed over its value heads in the kernel.  ``G`` and ``beta`` travel
as rows, one ``(2, Q)`` block a value head and chunk.

Every product is f32 at ``precision=HIGHEST`` with f32 accumulation, as
on the XLA path; PALLAS_NOTES.md has the grid, the VMEM budget and the
precision check on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import pallas_util

_LANES = 128
_HI = lax.Precision.HIGHEST
# value heads a key head: the states a grid step holds in VMEM
_MAX_HEADS = 8

_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b


def _why_not(k_shape, v_shape, v_dtype, chunk):
    """Why the kernel does not cover these shapes, or None."""
    (N, T, Hk, K), (Nv, Tv, Hv, V) = k_shape, v_shape
    if (N, T) != (Nv, Tv):
        return "shapes"
    if Hv % Hk:
        return "value heads not a multiple of key heads"
    if Hv // Hk > _MAX_HEADS:
        return "value heads a key head"
    if K % _LANES or V % _LANES:
        return "narrow head"
    if chunk % 16:
        return "chunk not a multiple of 16"
    if v_dtype is None or np.dtype(v_dtype) not in (np.dtype(jnp.float32),
                                                    np.dtype(jnp.bfloat16)):
        return "dtype"
    return None


def delta_rule_path(k_shape, v_shape, v_dtype, chunk):
    """``("kernel" | "xla", reason)``: which form the gated delta rule
    takes for ``q``, ``k`` (N, T, Hk, K) and ``v`` (N, T, Hv, V) of
    ``v_dtype`` in chunks of ``chunk``.  Static per compiled program, on
    shapes and dtypes alone: the kernel for key and value heads of whole
    128-lane groups, value heads a multiple (at most ``_MAX_HEADS``) of
    the key heads, a chunk of whole bf16 sublane tiles and ``v`` in f32
    or bf16 (any T: the wrapper pads it to whole chunks), where
    ``resolve_kernel_impl()`` says ``pallas``."""
    why_not = _why_not(k_shape, v_shape, v_dtype, chunk)
    if why_not:
        return "xla", why_not
    from bigdl_tpu.ops import resolve_kernel_impl
    if resolve_kernel_impl() != "pallas":
        return "xla", "kernel_impl xla"
    return "kernel", "aligned heads, whole chunks"


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _column(row):
    """A (1, Q) row as a (Q, 1) column: its diagonal, summed along the
    lanes (Mosaic transposes no vector this narrow)."""
    Q = row.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           == lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _chunk(S, q, k, v, T, G, b):
    """One chunk of the rule for the value heads of one key head.

    ``q``, ``k``: (Q, K) of the key head; per value head (tuples, one
    entry a head): ``S`` (K, V) the state the chunk enters with, ``v``
    (Q, V), ``T`` (Q, Q) the chunk's ``(I + A)^{-1}``, ``G`` (1, Q) the
    running sum of ``g``, ``b`` (1, Q) ``beta``.  Returns the heads'
    ``o`` (Q, V) and the states they leave with (K, V)."""
    Q, K = k.shape
    on_or_under = (lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
                   <= lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    last = lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    qk = _dot(q, k, _NT)
    outs, states = [], []
    for S_h, v_h, T_h, G_h, b_h in zip(S, v, T, G, b):
        V = v_h.shape[1]
        Gc = _column(G_h)
        eG = jnp.exp(Gc)
        G_end = jnp.sum(jnp.where(last, G_h, 0.0), axis=1, keepdims=True)
        # U | W in one product: beta scales T's columns
        uw = _dot(T_h * b_h, jnp.concatenate([v_h, eG * k], axis=1), _NN)
        # W S and (e^G q) S in one product of 2Q rows
        ws = _dot(jnp.concatenate([uw[:, V:], eG * q], axis=0), S_h, _NN)
        delta = uw[:, :V] - ws[:Q]
        decay = jnp.where(on_or_under,
                          jnp.exp(jnp.where(on_or_under, Gc - G_h, 0.0)),
                          0.0)
        outs.append(ws[Q:] + _dot(qk * decay, delta, _NN))
        states.append(jnp.exp(G_end) * S_h + _dot(
            jnp.exp(G_end - Gc) * k, delta, _TN))
    return tuple(outs), tuple(states)


def _read(q_ref, k_ref, v_ref, t_ref, gb_ref, r, V):
    """A grid step's key head and its ``r`` value heads as ``_chunk``
    takes them."""
    f32 = jnp.float32
    return (q_ref[...], k_ref[...],
            tuple(v_ref[:, h * V:(h + 1) * V].astype(f32) for h in range(r)),
            tuple(t_ref[h] for h in range(r)),
            tuple(gb_ref[h, 0:1, :] for h in range(r)),
            tuple(gb_ref[h, 1:2, :] for h in range(r)))


def _fwd_kernel(q_ref, k_ref, v_ref, t_ref, gb_ref, o_ref, *rest,
                r, V, keep):
    kept, s_ref = rest[:-1], rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    if keep:
        kept[0][...] = s_ref[...]       # the states the chunk enters with
    o, S = _chunk(tuple(s_ref[h] for h in range(r)),
                  *_read(q_ref, k_ref, v_ref, t_ref, gb_ref, r, V))
    for h in range(r):
        o_ref[:, h * V:(h + 1) * V] = o[h]
        s_ref[h] = S[h]


def _bwd_kernel(q_ref, k_ref, v_ref, t_ref, gb_ref, s_in_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dt_ref, dgb_ref, ds_ref,
                *, r, V):
    @pl.when(pl.program_id(2) == 0)
    def _start():                       # nothing reads the last state
        ds_ref[...] = jnp.zeros_like(ds_ref)

    _, vjp = jax.vjp(_chunk, tuple(s_in_ref[h] for h in range(r)),
                     *_read(q_ref, k_ref, v_ref, t_ref, gb_ref, r, V))
    dS, dq, dk, dv, dT, dG, db = vjp(
        (tuple(do_ref[:, h * V:(h + 1) * V] for h in range(r)),
         tuple(ds_ref[h] for h in range(r))))
    dq_ref[...] = dq                    # summed over the value heads
    dk_ref[...] = dk
    for h in range(r):
        dv_ref[:, h * V:(h + 1) * V] = dv[h].astype(dv_ref.dtype)
        dt_ref[h] = dT[h]
        dgb_ref[h, 0:1, :] = dG[h]
        dgb_ref[h, 1:2, :] = db[h]
        ds_ref[h] = dS[h]


def _specs(nc, Q, K, V, r, reverse):
    """Block specs, grid (batch, key head, chunk): the key head's ``(Q,
    K)``, its value heads' ``(Q, r V)``, and their per-chunk ``(Q, Q)``,
    ``(2, Q)`` and state blocks.  ``reverse`` walks the chunks from the
    last."""
    chunk = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    key = pl.BlockSpec((None, Q, K), lambda n, j, c: (n, chunk(c), j))
    val = pl.BlockSpec((None, Q, r * V), lambda n, j, c: (n, chunk(c), j))

    def per_head(shape):
        return pl.BlockSpec((None, r, None) + shape,
                            lambda n, j, c: (n, j, chunk(c), 0, 0))

    return key, val, per_head((Q, Q)), per_head((2, Q)), per_head((K, V))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _dims(q, v, T, K):
    """Batch, key heads, chunks, chunk, value width and value heads a
    key head of the kernel's operands (``q`` (N, Tp, Hk K), ``v`` (N,
    Tp, Hv V), ``T`` (N, Hv, c, Q, Q)) and the key width ``K``."""
    N, Hv, nc, Q = T.shape[:4]
    Hk = q.shape[-1] // K
    return N, Hk, nc, Q, v.shape[-1] // Hv, Hv // Hk


def _forward(q, k, v, T, gb, *, K, keep):
    """``q``, ``k``: (N, Tp, Hk K) f32; ``v``: (N, Tp, Hv V); ``T``:
    (N, Hv, c, Q, Q); ``gb``: (N, Hv, c, 2, Q), ``G`` over ``beta``.
    ``o`` (N, Tp, Hv V) f32 and, where ``keep``, the entering states
    (N, Hv, c, K, V) f32."""
    N, Hk, nc, Q, V, r = _dims(q, v, T, K)
    key, val, square, rows, state = _specs(nc, Q, K, V, r, False)
    f32 = jnp.float32
    Hv = Hk * r
    return pl.pallas_call(
        functools.partial(_fwd_kernel, r=r, V=V, keep=keep),
        grid=(N, Hk, nc),
        in_specs=[key, key, val, square, rows],
        out_specs=[val] + [state] * keep,
        out_shape=[jax.ShapeDtypeStruct((N, q.shape[1], Hv * V), f32)]
        + [jax.ShapeDtypeStruct((N, Hv, nc, K, V), f32)] * keep,
        scratch_shapes=[pltpu.VMEM((r, K, V), f32)],
        compiler_params=_params(),
        interpret=pallas_util.interpret_default(),
        name="gated_delta_rule_fwd",
    )(q, k, v, T, gb)


def _backward(q, k, v, T, gb, states, do, *, K):
    N, Hk, nc, Q, V, r = _dims(q, v, T, K)
    key, val, square, rows, state = _specs(nc, Q, K, V, r, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, r=r, V=V),
        grid=(N, Hk, nc),
        in_specs=[key, key, val, square, rows, state, val],
        out_specs=[key, key, val, square, rows],
        out_shape=[like(a) for a in (q, k, v, T, gb)],
        scratch_shapes=[pltpu.VMEM((r, K, V), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_util.interpret_default(),
        name="gated_delta_rule_bwd",
    )(q, k, v, T, gb, states, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, T, gb, K):
    return _forward(q, k, v, T, gb, K=K, keep=False)[0]


def _rule_fwd(q, k, v, T, gb, K):
    o, states = _forward(q, k, v, T, gb, K=K, keep=True)
    return o, (q, k, v, T, gb, states)


def _rule_bwd(K, kept, do):
    return _backward(*kept, do, K=K)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """The gated delta rule, chunked, through the kernel: ``q``, ``k``
    (N, T, Hk, K) per KEY head, already of unit length and scaled as
    the caller wants; ``v`` (N, T, Hv, V), value head ``h`` reading key
    head ``h // (Hv // Hk)``; ``g`` (N, T, Hv) the log-decay (<= 0);
    ``beta`` (N, T, Hv).  Returns ``o`` (N, T, Hv, V) in f32.  For
    arguments :func:`delta_rule_path` sends to the kernel.  T is padded
    to whole chunks with steps of ``g = 0`` and ``beta = 0``, which
    leave the state as it is."""
    N, T, Hk, K = q.shape
    Hv, V = v.shape[2:]
    f32 = jnp.float32
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (T + pad) // chunk
    q, k = q.astype(f32), k.astype(f32)

    def heads_first(a):             # (N, Tp, Hv) -> (N, Hv, c, Q)
        return jnp.moveaxis(a.astype(f32).reshape(N, nc, chunk, Hv), 3, 1)

    G = jnp.cumsum(heads_first(g), axis=-1)
    b = heads_first(beta)
    # the chunk's triangular system, per value head: its inverse, by
    # substitution against the identity
    under = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    kc = k.reshape(N, nc, chunk, Hk, K)
    kk = jnp.einsum("nctjk,ncsjk->njcts", kc, kc, precision=_HI)
    kk = jnp.repeat(kk, Hv // Hk, axis=1)
    A = jnp.where(under, b[..., None] * kk * jnp.exp(jnp.where(
        under, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    inverse = jax.lax.linalg.triangular_solve(
        A, jnp.broadcast_to(jnp.eye(chunk, dtype=f32), A.shape),
        left_side=True, lower=True, unit_diagonal=True)
    o = _rule(q.reshape(N, T + pad, Hk * K), k.reshape(N, T + pad, Hk * K),
              v.reshape(N, T + pad, Hv * V), inverse,
              jnp.stack([G, b], axis=-2), K)
    return o.reshape(N, T + pad, Hv, V)[:, :T]
