"""Multi-head latent attention (MLA; DeepSeek-AI: "DeepSeek-V2",
arXiv:2405.04434, section 2.1) without a query latent
(``q_lora_rank`` null), with a sigmoid gate a head on its output: the
full-attention layer of Ling-3.0-flash.

No reference analog.  Keys and values come out of ONE normed latent of
``latent`` channels; the rotary part of the key is ``rope_dim`` channels
shared by every head::

    q        = x W_q                     a head [nope | rope]
    [c; k_r] = x W_kva                   latent | rope_dim
    c        = rms(c) * w_c
    [k_n; v] = c W_kvb                   a head [nope | value]
    q_r, k_r = rope(q_r), rope(k_r)      k_r one for all heads
    o_h      = causal softmax([q_n; q_r] [k_n; k_r]^T / sqrt(nope + rope)) v
    out      = (o_h * sigmoid(x w_g,h)) W_o      one gate a head

Keys are ``nope + rope`` channels wide and values ``value_dim``: no
flash kernel takes two widths (``ops.pallas_attention.attention_path``
is handed no single dtype for them and answers ``"dtype"``), so the
layer takes ``dot_product_attention(q_block=)``'s XLA loop over blocks
of queries.  The cache a decoder would keep (``c`` and ``k_r``, 576
numbers a position) is not built: nothing here serves."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import dot_product_attention, rms_norm, rope
from bigdl_tpu.nn.module import Module
from bigdl_tpu.telemetry.scopes import device_scope


class MultiHeadLatentAttention(Module):
    """Causal latent attention (module docstring): ``heads`` heads whose
    queries and keys are ``nope_dim + rope_dim`` channels and values
    ``value_dim``, keys and values from a latent of ``latent`` channels.
    ``theta``: the rotary base.  ``q_block``: the block of queries of the
    XLA path (``None``: all scores at once).  Every head is held: the
    deployment this layer is built for runs it data parallel.  Input and
    output: (N, T, D).  Weights are stored (in, out), drawn N(0, 0.02)
    (``initializer_range``); the latent's norm weight starts at 1."""

    def __init__(self, hidden: int, heads: int, *, latent: int,
                 nope_dim: int, rope_dim: int, value_dim: int,
                 theta: float = 10000.0, q_block: Optional[int] = 1024,
                 eps: float = 1e-6, name: Optional[str] = None):
        super().__init__(name)
        self.hidden, self.heads, self.latent = hidden, heads, latent
        self.nope_dim, self.rope_dim = nope_dim, rope_dim
        self.value_dim, self.theta = value_dim, theta
        self.q_block, self.eps = q_block, eps
        self.qk_dim = nope_dim + rope_dim

    def init(self, rng):
        D, H, r = self.hidden, self.heads, self.latent
        ks = jax.random.split(rng, 5)
        normal = lambda k, shape: 0.02 * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        return {"wq": normal(ks[0], (D, H * self.qk_dim)),
                "wkv_a": normal(ks[1], (D, r + self.rope_dim)),
                "kv_norm": jnp.ones((r,), jnp.float32),
                "wkv_b": normal(ks[2], (r, H * (self.nope_dim
                                                + self.value_dim))),
                "wg": normal(ks[3], (D, H)),
                "wo": normal(ks[4], (H * self.value_dim, D))}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T, _ = input.shape
        H, r, dn, dr = self.heads, self.latent, self.nope_dim, self.rope_dim
        pos = jnp.arange(T)
        with device_scope("mla.project"):
            q = (input @ params["wq"]).reshape(N, T, H, self.qk_dim) \
                .transpose(0, 2, 1, 3)
            ckr = input @ params["wkv_a"]
            c = rms_norm(ckr[..., :r], params["kv_norm"], self.eps)
            kv = (c @ params["wkv_b"]).reshape(N, T, H, dn + self.value_dim) \
                .transpose(0, 2, 1, 3)
            k_r = rope(ckr[:, None, :, r:], pos, self.theta)
            q = jnp.concatenate(
                [q[..., :dn], rope(q[..., dn:], pos, self.theta)], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r, (N, H, T, dr))], axis=-1)
            v = kv[..., dn:]
        with device_scope("mla.attend"):
            o = dot_product_attention(q, k, v, causal=True,
                                      scale=1.0 / math.sqrt(self.qk_dim),
                                      q_block=self.q_block)
        with device_scope("mla.project"):
            gate = jax.nn.sigmoid((input @ params["wg"]).astype(jnp.float32))
            o = o.transpose(0, 2, 1, 3) * gate[..., None].astype(o.dtype)
            return o.reshape(N, T, H * self.value_dim) @ params["wo"], state
