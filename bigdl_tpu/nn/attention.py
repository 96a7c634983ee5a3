"""Attention layers.

No reference analog — BigDL v0.x predates transformers (SURVEY §5:
"no attention, no ring/Ulysses/blockwise anything") — but long-context and
distributed are first-class in the TPU build, so attention is core nn
surface.  Sequence-parallel execution lives in
``bigdl_tpu.parallel.ring_attention``; this module is the single-device
math it distributes.

Layout: (N, T, D) batch-major, heads split internally to (N, H, T, Dh).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.telemetry.scopes import device_scope


class LayerNorm(Module):
    """Layer normalization over the last dim (standard transformer norm;
    the reference's closest is ``Normalize``)."""

    def __init__(self, normalized_size: int, eps: float = 1e-5,
                 name: Optional[str] = None):
        super().__init__(name)
        self.size = normalized_size
        self.eps = eps

    def init(self, rng):
        return {"weight": jnp.ones((self.size,), jnp.float32),
                "bias": jnp.zeros((self.size,), jnp.float32)}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        # normalize in f32 for bf16 stability, cast back
        x = input.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["weight"] + params["bias"]
        return y.astype(input.dtype), state


class RMSNorm(Module):
    """Root-mean-square normalization over the last dim:
    ``x * rsqrt(mean(x^2) + eps) * weight``, no mean taken off and no
    bias (Zhang & Sennrich 2019; the norm of the Llama / Granite
    families).  Statistics in f32, result in the input's dtype."""

    def __init__(self, normalized_size: int, eps: float = 1e-5,
                 name: Optional[str] = None):
        super().__init__(name)
        self.size = normalized_size
        self.eps = eps

    def init(self, rng):
        return {"weight": jnp.ones((self.size,), jnp.float32)}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        return rms_norm(input, params["weight"], self.eps), state


def rms_norm(x, weight, eps: float):
    """The arithmetic of :class:`RMSNorm` on bare arrays."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _attend(q, k, v, mask, *, causal: bool, scale: float, q_pos0: int):
    """One block of queries against the keys it may see.  ``q``:
    (N, H, Tq, Dh); ``k``, ``v``: (N, Hkv, Tk, Dh) with H a multiple of
    Hkv; ``q_pos0``: the key position the block's first query sits at."""
    N, H, Tq, Dh = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    # grouped queries: the H // Hkv query heads of a group share the
    # group's one key/value head; nothing is repeated in memory
    qg = q.reshape(N, Hkv, H // Hkv, Tq, Dh)
    scores = jnp.einsum("ngrqd,ngkd->ngrqk", qg, k).astype(jnp.float32) \
        * scale
    if causal:
        qi = jnp.arange(Tq)[:, None] + q_pos0
        ki = jnp.arange(Tk)[None, :]
        scores = jnp.where(ki <= qi, scores, -jnp.inf)
    if mask is not None:
        mask = jnp.broadcast_to(mask, (N, H, Tq, Tk)).reshape(scores.shape)
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("ngrqk,ngkd->ngrqd", w, v).reshape(N, H, Tq, Dh)


def dot_product_attention(q, k, v, *, causal: bool = False,
                          mask: Optional[jnp.ndarray] = None,
                          scale: Optional[float] = None,
                          q_block: Optional[int] = None):
    """Softmax attention.  ``q``: (N, H, Tq, Dh); ``k``, ``v``:
    (N, Hkv, Tk, Dh) where ``Hkv`` divides ``H`` (grouped-query
    attention: query head ``h`` reads key/value head ``h // (H // Hkv)``;
    ``Hkv == H`` is plain multi-head attention).  Softmax statistics in
    f32 (bf16-safe).

    ``q_block``: compute the queries a block of that many at a time, so
    that the scores alive at once are (N, H, q_block, Tk) and not
    (N, H, Tq, Tk); each block is a ``jax.checkpoint``, so the backward
    pass recomputes a block's scores and keeps none.  A causal block
    reads only the keys at or before its last query.  ``None`` (or a
    block that holds every query) is the one-block computation."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    H, Hkv = q.shape[1], k.shape[1]
    if H % Hkv:
        raise ValueError(f"{H} query heads cannot share {Hkv} key/value "
                         "heads: not a multiple")
    Tq, Tk = q.shape[-2], k.shape[-2]
    # offset supports Tq != Tk (decode: query tail of the sequence)
    offset = Tk - Tq
    if q_block is None or q_block >= Tq:
        return _attend(q, k, v, mask, causal=causal, scale=scale,
                       q_pos0=offset)
    if mask is not None:
        mask = jnp.broadcast_to(mask, mask.shape[:-2] + (Tq, Tk))
    outs = []
    for lo in range(0, Tq, q_block):
        hi = min(lo + q_block, Tq)
        # keys after a causal block's last query are masked anyway
        k_end = min(hi + offset, Tk) if causal else Tk
        block = jax.checkpoint(functools.partial(
            _attend, causal=causal, scale=scale, q_pos0=lo + offset))
        outs.append(block(
            q[..., lo:hi, :], k[..., :k_end, :], v[..., :k_end, :],
            None if mask is None else mask[..., lo:hi, :k_end]))
    return jnp.concatenate(outs, axis=-2)


class MultiHeadAttention(Module):
    """Multi-head self/cross attention with fused qkv projection.

    Input: tensor (N, T, D) for self-attention, or a (query, kv) tuple for
    cross-attention."""

    def __init__(self, embed_dim: int, num_heads: int,
                 causal: bool = False, with_bias: bool = True,
                 dropout: float = 0.0, shard: bool = False,
                 name: Optional[str] = None):
        super().__init__(name)
        assert embed_dim % num_heads == 0
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.causal = causal
        self.with_bias = with_bias
        self.dropout = dropout
        # tensor parallelism (Megatron attention split): heads sharded over
        # the `model` mesh axis via qkv column / output row parallel specs
        self.shard = shard

    def param_specs(self):
        """Weights here are stored (in, out) and used as x @ W, so the
        output-dim split is dim 1 (vs dim 0 for Linear's (out, in))."""
        if not self.shard:
            return None
        from jax.sharding import PartitionSpec as P
        sp = {"wq": P(None, "model"), "wk": P(None, "model"),
              "wv": P(None, "model"), "wo": P("model", None)}
        if self.with_bias:
            sp.update({"bq": P("model"), "bk": P("model"),
                       "bv": P("model"), "bo": P()})
        return sp

    def init(self, rng):
        D = self.embed_dim
        ks = jax.random.split(rng, 4)
        xav = Xavier()
        params = {
            "wq": xav.init(ks[0], (D, D), D, D),
            "wk": xav.init(ks[1], (D, D), D, D),
            "wv": xav.init(ks[2], (D, D), D, D),
            "wo": xav.init(ks[3], (D, D), D, D),
        }
        if self.with_bias:
            for n in ("bq", "bk", "bv", "bo"):
                params[n] = jnp.zeros((D,), jnp.float32)
        return params, {}

    def _split(self, x):
        N, T, _ = x.shape
        return x.reshape(N, T, self.num_heads, self.head_dim) \
                .transpose(0, 2, 1, 3)

    def apply(self, params, state, input, *, training=False, rng=None):
        if isinstance(input, (tuple, list)):
            xq, xkv = input
        else:
            xq = xkv = input
        q = xq @ params["wq"]
        k = xkv @ params["wk"]
        v = xkv @ params["wv"]
        if self.with_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        q, k, v = self._split(q), self._split(k), self._split(v)
        o = dot_product_attention(q, k, v, causal=self.causal)
        if self.dropout > 0 and training:
            if rng is None:
                raise ValueError("attention dropout needs an rng")
            keep = 1.0 - self.dropout
            o = jnp.where(jax.random.bernoulli(rng, keep, o.shape),
                          o / keep, 0.0)
        N, H, T, Dh = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(N, T, H * Dh)
        out = o @ params["wo"]
        if self.with_bias:
            out = out + params["bo"]
        return out, state


class GroupedQueryAttention(Module):
    """Causal grouped-query self-attention without bias and without a
    positional encoding (NoPE): ``num_heads`` query heads of ``head_dim``
    on ``num_kv_heads`` key/value heads, scores times ``scale``
    (default ``head_dim ** -0.5``), computed ``q_block`` queries at a
    time (:func:`dot_product_attention`).

    ``held=(lo, hi)``: the key/value groups THIS chip holds of a
    tensor-parallel layer (default: all).  The projections then have the
    held heads' columns (q, k, v) and rows (o) only, and the output is
    this share's partial sum: the shares of all chips add up to the
    layer's output (the all-reduce is the caller's; on one chip the
    layer runs without it).

    Input and output: (N, T, D).  Weights are stored (in, out)."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: Optional[int] = None, *, held=None,
                 scale: Optional[float] = None,
                 q_block: Optional[int] = 1024,
                 name: Optional[str] = None):
        super().__init__(name)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads on {num_kv_heads} "
                             "key/value heads: not a multiple")
        self.embed_dim = embed_dim
        self.head_dim = head_dim or embed_dim // num_heads
        self.group = num_heads // num_kv_heads
        lo, hi = held if held is not None else (0, num_kv_heads)
        if not 0 <= lo < hi <= num_kv_heads:
            raise ValueError(f"held key/value heads {held} outside "
                             f"[0, {num_kv_heads}]")
        self.held = (lo, hi)
        self.kv_heads = hi - lo
        self.q_heads = self.kv_heads * self.group
        self.scale = scale if scale is not None \
            else 1.0 / math.sqrt(self.head_dim)
        self.q_block = q_block

    def init(self, rng):
        D, Dh = self.embed_dim, self.head_dim
        ks = jax.random.split(rng, 4)
        xav = Xavier()
        nq, nkv = self.q_heads * Dh, self.kv_heads * Dh
        return {"wq": xav.init(ks[0], (D, nq), D, nq),
                "wk": xav.init(ks[1], (D, nkv), D, nkv),
                "wv": xav.init(ks[2], (D, nkv), D, nkv),
                "wo": xav.init(ks[3], (nq, D), nq, D)}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T, _ = input.shape

        def heads(x, n):
            return x.reshape(N, T, n, self.head_dim).transpose(0, 2, 1, 3)

        with device_scope("attention"):
            q = heads(input @ params["wq"], self.q_heads)
            k = heads(input @ params["wk"], self.kv_heads)
            v = heads(input @ params["wv"], self.kv_heads)
            o = dot_product_attention(q, k, v, causal=True,
                                      scale=self.scale,
                                      q_block=self.q_block)
            o = o.transpose(0, 2, 1, 3).reshape(N, T, -1)
            return o @ params["wo"], state
