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
from bigdl_tpu.nn.mamba import causal_depthwise_conv1d
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


def rope(x, positions, theta: float, rotary_dim: Optional[int] = None):
    """Rotary positions (Su et al., arXiv:2104.09864) on the first
    ``rotary_dim`` channels of each head (default: all), HF's
    rotate-half pairing: channel ``i`` turns with channel ``i +
    rotary_dim / 2`` by the angle ``position * theta ** (-2 i /
    rotary_dim)``; the channels past ``rotary_dim`` are left as they
    are.  ``x``: (..., T, Dh); ``positions``: (T,).  Angles in f32,
    result in ``x``'s dtype."""
    rd = x.shape[-1] if rotary_dim is None else rotary_dim
    half = rd // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rd)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq   # (T, half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:rd], x32[..., rd:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1).astype(x.dtype)


class CompressedConvAttention(Module):
    """Compressed convolutional attention (Zyphra, arXiv:2510.04476; the
    attention of ZAYA1, arXiv:2511.17127): causal grouped-query
    attention carried out in a latent of ``heads * head_dim`` (queries)
    and ``kv_heads * head_dim`` (keys, values) channels, narrower than
    ``hidden``, whose queries and keys are mixed over time and
    normalised before they meet::

        q~ = a W_q;  k~ = a W_k;  v = heads([a_t W_v1 ; a_{t-1} W_v2])
        u  = conv_grouped(conv_depthwise([q~ ; k~]))     causal, with bias
        m_q = (heads(q~) + repeat(heads(k~))) / 2;  m_k = mean over a group
        q  = rms(heads(u_q) + m_q);  k = exp(t) * rms(heads(u_k) + m_k)
        q, k = rope(q), rope(k)  on the first ``rotary`` channels a head
        out = (causal softmax(q k^T / sqrt(head_dim)) v) W_o

    ``conv=(k0, k1)``: the taps of the depthwise convolution (a channel
    alone) and of the grouped one (the ``head_dim`` channels of one head
    mix among themselves); ``[q~ ; k~]`` is padded ONCE, with ``k0 - 1 +
    k1 - 1`` zeros, and both convolutions run without padding of their
    own, so position ``t`` reads ``t - (k0 + k1 - 2) .. t``.  ``rms`` is
    ``sqrt(head_dim) x / |x|_2`` with no weight, in f32; ``t`` one
    learned scalar a key/value head, 0 at the start.  Half of the value
    channels of the WHOLE layer (the last ``kv_heads * head_dim / 2``)
    come from the previous token (zeros before the first).  ``rotary``:
    ``(theta, channels)``.

    ``held=(lo, hi)``: the key/value groups THIS chip holds (as
    :class:`GroupedQueryAttention`): their columns of ``W_q``, ``W_k``
    and ``[W_v1 ; W_v2]``, the convolutions' channels and groups, their
    ``t``, their rows of ``W_o``; the output is the share's partial sum.

    Input and output: (N, T, D).  Weights are stored (in, out)."""

    def __init__(self, hidden: int, heads: int, kv_heads: int,
                 head_dim: int, *, conv=(2, 2), rotary=(10000.0, None),
                 held=None, q_block: Optional[int] = 1024,
                 eps: float = 1e-5, name: Optional[str] = None):
        super().__init__(name)
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads on {kv_heads} "
                             "key/value heads: not a multiple")
        lo, hi = held if held is not None else (0, kv_heads)
        if not 0 <= lo < hi <= kv_heads:
            raise ValueError(f"held key/value heads {held} outside "
                             f"[0, {kv_heads}]")
        self.hidden, self.head_dim = hidden, head_dim
        self.group = heads // kv_heads
        self.held = (lo, hi)
        self.kv_heads = hi - lo
        self.q_heads = self.kv_heads * self.group
        self.conv, self.eps, self.q_block = tuple(conv), eps, q_block
        self.theta, self.rotary_dim = rotary
        # the held value channels that read the token itself: those in
        # the first half of the whole layer's kv_heads * head_dim
        first, half = lo * head_dim, kv_heads * head_dim // 2
        self.v_now = min(max(half - first, 0), self.kv_heads * head_dim)

    def init(self, rng):
        D, dh = self.hidden, self.head_dim
        nq, nkv = self.q_heads * dh, self.kv_heads * dh
        k0, k1 = self.conv
        ks = jax.random.split(rng, 8)
        xav = Xavier()
        # the convolutions as torch's Conv1d draws them: uniform within
        # 1 / sqrt(taps x channels read)
        b0, b1 = 1.0 / math.sqrt(k0), 1.0 / math.sqrt(k1 * dh)
        uniform = functools.partial(jax.random.uniform, dtype=jnp.float32)
        return {"wq": xav.init(ks[0], (D, nq), D, nq),
                "wk": xav.init(ks[1], (D, nkv), D, nkv),
                "wv": xav.init(ks[2], (D, nkv), D, nkv),
                "conv0_w": uniform(ks[3], (k0, nq + nkv), minval=-b0,
                                   maxval=b0),
                "conv0_b": uniform(ks[4], (nq + nkv,), minval=-b0,
                                   maxval=b0),
                "conv1_w": uniform(ks[5], (k1, self.q_heads + self.kv_heads,
                                           dh, dh), minval=-b1, maxval=b1),
                "conv1_b": uniform(ks[6], (nq + nkv,), minval=-b1,
                                   maxval=b1),
                "temp": jnp.zeros((self.kv_heads,), jnp.float32),
                "wo": xav.init(ks[7], (nq, D), nq, D)}, {}

    def mix(self, params, q0, k0):
        """``(q, k)`` as (N, T, heads, Dh), ready to attend, of the
        projections ``q0`` (N, T, q_heads * Dh) and ``k0``."""
        N, T, _ = q0.shape
        dh, G, hq, hkv = self.head_dim, self.group, self.q_heads, \
            self.kv_heads
        taps = self.conv[1]
        u = jnp.pad(jnp.concatenate([q0, k0], axis=-1),
                    ((0, 0), (taps - 1, 0), (0, 0)))
        u = causal_depthwise_conv1d(u, params["conv0_w"], params["conv0_b"])
        u = u.reshape(N, T + taps - 1, hq + hkv, dh)
        u = sum(jnp.einsum("nthd,hde->nthe", u[:, j:j + T],
                           params["conv1_w"][j]) for j in range(taps)) \
            + params["conv1_b"].reshape(hq + hkv, dh)
        qh = q0.reshape(N, T, hkv, G, dh)
        kh = k0.reshape(N, T, hkv, dh)
        m_q = (qh + kh[:, :, :, None]) / 2
        q = u[:, :, :hq] + m_q.reshape(N, T, hq, dh)
        k = u[:, :, hq:] + jnp.mean(m_q, axis=3)

        def unit(x):        # sqrt(Dh) x / |x| = an RMS norm of weight 1
            return rms_norm(x.astype(jnp.float32),
                            jnp.ones((), jnp.float32), self.eps)

        k = unit(k) * jnp.exp(params["temp"].astype(jnp.float32))[:, None]
        pos = jnp.arange(T)
        turn = lambda x: rope(x.transpose(0, 2, 1, 3), pos, self.theta,
                              self.rotary_dim).astype(q0.dtype)
        return turn(unit(q)), turn(k)

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T, _ = input.shape
        dh = self.head_dim
        with device_scope("cca.project"):
            q0, k0 = input @ params["wq"], input @ params["wk"]
            v = input @ params["wv"]
            # (a_{t-1}) W = (a W)_{t-1}: the shift after the product
            before = jnp.pad(v[:, :-1, self.v_now:], ((0, 0), (1, 0), (0, 0)))
            v = jnp.concatenate([v[..., :self.v_now], before], axis=-1) \
                .reshape(N, T, self.kv_heads, dh).transpose(0, 2, 1, 3)
        with device_scope("cca.mix"):
            q, k = self.mix(params, q0, k0)
        with device_scope("cca.attend"):
            o = dot_product_attention(q, k, v, causal=True,
                                      q_block=self.q_block)
        with device_scope("cca.out"):
            o = o.transpose(0, 2, 1, 3).reshape(N, T, -1)
            return o @ params["wo"], state
