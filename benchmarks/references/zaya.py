"""The plain reference of ``zaya`` (Zyphra's ZAYA1; the product's model
is ``bigdl_tpu/models/zaya.py``): ``jax.numpy`` only, no kernel, no
grouped product, nothing imported from the product.  The equations are
ISSUE 35's, after "Compressed Convolutional Attention"
(arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127):

- the convolutions are explicit shifted sums over the ONCE-padded
  ``[q~ ; k~]`` (``k0 - 1 + k1 - 1`` zeros in front);
- the value's second half is the projection of the PREVIOUS token's
  input (the input shifted, then projected);
- attention is the full score matrix against all T keys, a block of
  queries at a time, keys and values repeated for the query heads that
  share them;
- the experts are a loop over the experts held, each run on EVERY token
  and weighted by that token's gate for it, 0 where it was not chosen;
- the router is written out step by step in ``f32`` at the highest
  precision.

It reads the product's parameter tree (weights stored (in, out)) and is
given the same share: how many heads, experts and vocabulary rows are
held is read from the shapes, WHICH heads and experts from ``share``
(the value half a head reads depends on which head of the whole layer
it is).

Departures from "float32, highest precision", each forced and noted:
(1) the arithmetic follows the parameters' dtype, so that on the chip it
can run in the cell's stated arithmetic — bf16 products over f32 master
parameters, with norms, softmax, the router, the L2 normalisation and
the temperature, the rotary angles, criterion and update in ``f32``
(``loss_fn`` casts).  On the CPU the tests hand it f32 parameters under
``jax.default_matmul_precision("highest")``.  ``f32`` is a module
global: ``tools/precision_reading.py`` sets it to bf16 to read what one
precision below gives.  (2) To fit 16,384 tokens and six layers on one
chip beside 2.8 GB of parameters and as much of gradients, a layer, a
block of queries and an expert's pass over all tokens are each a
``jax.checkpoint``: the same numbers, recomputed."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def rms(x, w, eps):
    x32 = x.astype(f32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(f32)).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def shifted(x, by: int):
    """``x`` (N, T, C) moved ``by`` positions later, zeros in front."""
    T = x.shape[1]
    return jnp.pad(x, ((0, 0), (by, 0), (0, 0)))[:, :T]


# ------------------------------------------------------------ attention
def rotate(x, theta, rd):
    """HF's ``apply_rotary_pos_emb`` on the first ``rd`` channels of
    ``x`` (N, T, H, Dh): ``x cos + rotate_half(x) sin``."""
    T = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xr, rest = x[..., :rd], x[..., rd:]
    half = jnp.concatenate([-xr[..., rd // 2:], xr[..., :rd // 2]], axis=-1)
    return jnp.concatenate([xr * jnp.cos(ang) + half * jnp.sin(ang), rest],
                           axis=-1)


def mix(config, p, q0, k0):
    """``[q~ ; k~]`` -> the queries and keys that attend, (N, T, H, Dh):
    two convolutions, the query-key mean, unit length, temperature,
    rotary positions."""
    N, T, _ = q0.shape
    dh = config["head_dim"]
    G = config["num_attention_heads"] // config["num_key_value_heads"]
    t0, t1 = config["cca_time0"], config["cca_time1"]
    hkv = k0.shape[-1] // dh
    hq = hkv * G
    u = jnp.concatenate([q0, k0], axis=-1)
    # position s of the padded sequence is position s - pad of u
    pad = t0 - 1 + t1 - 1
    # depthwise: u1[s] = b0 + sum_j w0[j] * padded[s + j], s = 0..T+t1-2
    # grouped:   u2[t] = b1 + sum_i u1[t + i] W1[i],       t = 0..T-1
    # u1[t + i] reads padded[t + i + j] = u[t + i + j - pad]; where the
    # padded index falls before u[0] the tap reads 0 (the bias stays)
    u2 = 0.0
    for i in range(t1):
        u1 = sum(shifted(u, pad - i - j) * p["conv0_w"][j]
                 for j in range(t0)) + p["conv0_b"]
        u1 = u1.reshape(N, T, hq + hkv, dh)
        u2 = u2 + jnp.einsum("nthd,hde->nthe", u1, p["conv1_w"][i])
    u2 = u2 + p["conv1_b"].reshape(hq + hkv, dh)
    qh = q0.reshape(N, T, hq, dh)
    kh = k0.reshape(N, T, hkv, dh)
    m_q = (qh + jnp.repeat(kh, G, axis=2)) / 2
    m_k = jnp.mean(m_q.reshape(N, T, hkv, G, dh), axis=3)
    q = (u2[:, :, :hq] + m_q).astype(f32)
    k = (u2[:, :, hq:] + m_k).astype(f32)
    eps = config["rms_norm_eps"]
    unit = lambda x: math.sqrt(dh) * x / jnp.sqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + dh * eps)
    q = unit(q)
    k = unit(k) * jnp.exp(p["temp"].astype(f32))[None, None, :, None]
    rp = config["rope_parameters"]["hybrid"]
    rd = int(dh * rp["partial_rotary_factor"])
    return (rotate(q, rp["rope_theta"], rd).astype(q0.dtype),
            rotate(k, rp["rope_theta"], rd).astype(q0.dtype))


def attention(config, p, a, first_kv: int = 0):
    """Compressed convolutional attention of the heads held (read from
    the shapes; ``first_kv``: which key/value head of the whole layer
    the first held one is).  ``a``: (N, T, D)."""
    N, T, _ = a.shape
    dh = config["head_dim"]
    G = config["num_attention_heads"] // config["num_key_value_heads"]
    hkv = p["wk"].shape[1] // dh
    q0, k0 = a @ p["wq"], a @ p["wk"]
    # the whole layer's value channels: the first half from the token,
    # the second from the one before it
    half = config["num_key_value_heads"] * dh // 2
    now = min(max(half - first_kv * dh, 0), hkv * dh)
    v = jnp.concatenate([a @ p["wv"][:, :now],
                         shifted(a, 1) @ p["wv"][:, now:]], axis=-1)
    q, k = mix(config, p, q0, k0)
    v = v.reshape(N, T, hkv, dh)
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)

    @jax.checkpoint
    def block(qb, lo):
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k).astype(f32) / math.sqrt(dh)
        qpos = lo + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qpos, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("nhqk,nkhd->nqhd", w, v)

    o = jnp.concatenate([block(q[:, lo:lo + QUERY_BLOCK], lo)
                         for lo in range(0, T, QUERY_BLOCK)], axis=1)
    return o.reshape(N, T, hkv * G * dh) @ p["wo"]


# ------------------------------------------------------- router, experts
def router(config, p, x, r_prev):
    """``(probabilities over all experts (T, E), the experts chosen
    (T, k), r (T, Rr))`` of tokens ``x`` (T, D); ``r_prev`` is None in
    the first layer.  All f32."""
    w = {name: a.astype(f32) for name, a in p.items()}
    dot = lambda a, b: jnp.dot(a, b, precision=_HI)
    r = dot(x.astype(f32), w["wd"]) + w["bd"]
    if r_prev is not None:
        r = r + w["g"] * r_prev.astype(f32)
    z = rms(r, w["norm"], config["rms_norm_eps"])
    z = gelu(dot(z, w["w1"]) + w["b1"])
    z = gelu(dot(z, w["w2"]) + w["b2"])
    prob = jax.nn.softmax(dot(z, w["w3"]), axis=-1)
    # the balancing buffer is zeros and its controller is not built
    _, chosen = jax.lax.top_k(prob, config["num_experts_per_tok"])
    return prob, chosen, r


def routed_experts(config, p, x, r_prev, first_expert: int):
    """What the held experts (``first_expert`` and the following, as
    many as ``p`` holds) add for tokens ``x`` (N, T, D): every one of
    them on every token, times the token's gate for it (its probability
    over ALL experts) or 0.  Returns that and the router's state."""
    N, T, D = x.shape
    x = x.reshape(N * T, D)
    prob, chosen, r = router(
        config, p["router"], x,
        None if r_prev is None else r_prev.reshape(N * T, -1))
    out = jnp.zeros((N * T, D), f32)

    @jax.checkpoint
    def one(w_in, w_out, weight):
        h = x @ w_in
        half = h.shape[-1] // 2
        y = (silu(h[..., :half]) * h[..., half:]) @ w_out
        return y.astype(f32) * weight[:, None]

    for j in range(p["w_in"].shape[0]):
        e = first_expert + j
        weight = jnp.sum(jnp.where(chosen == e, prob[:, e:e + 1], 0.0),
                         axis=-1)
        out = out + one(p["w_in"][j], p["w_out"][j], weight)
    return out.astype(x.dtype).reshape(N, T, D), r.reshape(N, T, -1)


# ------------------------------------------------------------ the model
def layer(config, p, h, r_prev, share):
    index, of = share
    eps = config["rms_norm_eps"]
    y = attention(config, p["attention"], rms(h, p["norm1"], eps),
                  index * (config["num_key_value_heads"] // of))
    h = (p["s1"] * h + p["c1"]) + (p["s2"] * y + p["c2"])
    y, r = routed_experts(config, p["experts"], rms(h, p["norm2"], eps),
                          r_prev, index * (config["num_experts"] // of))
    return (p["s3"] * h + p["c3"]) + (p["s4"] * y + p["c4"]), r


def forward(config, share, params, ids):
    """Token ids (N, T), rows of the held slice -> logits (N, T, rows
    held) in f32, of the share ``(index, of)``."""
    h, r = params["embed"][ids], None
    for j in range(config["num_hidden_layers"]):
        run = jax.checkpoint(lambda p, x, s: layer(config, p, x, s, share))
        h, r = run(params["layers"][str(j)], h, r)
    x = rms(h, params["final_norm"], config["rms_norm_eps"])
    return jnp.einsum("ntd,vd->ntv", x, params["embed"],
                      preferred_element_type=f32)


def cross_entropy(logits, targets):
    """Mean over all tokens of ``-log softmax(logits)[target]``, f32."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(config, share, params, ids, targets, compute_dtype=None):
    """The training loss on f32 master ``params``: forward (and, through
    ``jax.grad``, backward) in ``compute_dtype``, criterion in f32."""
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    return cross_entropy(forward(config, share, params, ids), targets)


def make_sgd_step(config, share, compute_dtype=None):
    """``(params, ids, targets, lr) -> (loss, params - lr * grad)``: one
    jitted step that updates the donated parameters in place."""

    def step(params, ids, targets, lr):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(config, share, p, ids, targets,
                              compute_dtype))(params)
        return loss, jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                            params, grads)

    return jax.jit(step, donate_argnums=0)
