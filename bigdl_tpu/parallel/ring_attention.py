"""Ring attention — sequence/context parallelism over a mesh axis.

No reference analog (SURVEY §5: long-context absent from BigDL) — required
first-class capability of the TPU build: sequences longer than one chip's
HBM are sharded over the ``seq`` mesh axis, and attention runs blockwise
while K/V shards rotate around the ring via ``lax.ppermute`` over ICI
(Liu et al., "Ring Attention with Blockwise Transformers", 2023 — listed
in PAPERS.md retrieval set as the standard technique).

The online-softmax accumulation (running max ``m``, normalizer ``l``,
unnormalized output ``o``) makes each block's contribution exact, so the
result equals full attention bit-for-bit up to float associativity.

Compute/communication overlap: each step's K/V rotation is issued as the
same XLA program as the block matmuls; XLA schedules the ppermute
concurrently with compute (ICI DMA), which is the standard ring pipeline.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def _block(q, k, v, m, l, o, scale, mask):
    """One blockwise-attention accumulation step (online softmax).

    q: (B, H, Tq, D); k,v: (B, H, Tk, D); m,l: (B, H, Tq); o like q but f32.
    mask: (Tq, Tk) bool, True = attend."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp(-inf - -inf) guard: rows with no attendable keys yet keep m=-inf
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask, p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_new, l_new, o_new


def _ring_attn_local(q, k, v, *, axis_name: str, batch_axis: str,
                     causal: bool, scale: float):
    """Per-shard body (runs under shard_map).  q,k,v: (B, H, T_loc, D)
    local shards; sequence dim globally sharded over ``axis_name``."""
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape

    # mark accumulators device-varying over every mesh axis the inputs are
    # sharded on, so the fori_loop carry types match (shard_map
    # varying-manual-axes check, jax >= 0.8)
    axes = (batch_axis, axis_name)
    m0 = lax.pcast(jnp.full((B, H, T), -jnp.inf, jnp.float32), axes,
                   to="varying")
    l0 = lax.pcast(jnp.zeros((B, H, T), jnp.float32), axes, to="varying")
    o0 = lax.pcast(jnp.zeros((B, H, T, D), jnp.float32), axes, to="varying")

    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    qpos = my_idx * T + jnp.arange(T)

    def attend(step, k_cur, v_cur, m, l, o):
        # K/V currently held came from shard (my_idx - step) mod p
        src = (my_idx - step) % p_size
        kpos = src * T + jnp.arange(T)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = jnp.ones((T, T), bool)
        return _block(q, k_cur, v_cur, m, l, o, scale, mask)

    # step 0 attends to the local K/V; each later step rotates first —
    # p_size-1 rotations total, none wasted
    m, l, o = attend(0, k, v, m0, l0, o0)

    def body(step, carry):
        k_cur, v_cur, m, l, o = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        m, l, o = attend(step, k_cur, v_cur, m, l, o)
        return (k_cur, v_cur, m, l, o)

    if p_size > 1:
        _, _, m, l, o = lax.fori_loop(1, p_size, body, (k, v, m, l, o))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
                   batch_axis: str = "data", causal: bool = False,
                   scale: Optional[float] = None):
    """Sequence-parallel attention.  q,k,v: (B, H, T, D) with T sharded
    over ``mesh[seq_axis]`` (batch may be sharded over ``batch_axis``)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(batch_axis, None, seq_axis, None)
    fn = shard_map(
        functools.partial(_ring_attn_local, axis_name=seq_axis,
                          batch_axis=batch_axis, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
