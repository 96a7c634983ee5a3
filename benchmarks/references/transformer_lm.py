"""The plain reference of a GPT-2-shaped decoder: learned positions,
pre-norm blocks (``x + attn(ln(x))``, ``x + mlp(ln(x))``), ``gelu_new``,
a final LayerNorm and an output product, written out in ``jax.numpy``
with full causal attention, no cache, no batching of requests and
nothing imported from the program under test.

It also DRAWS the weights (:func:`draw`): the reference may take nothing
the program has made, so the benchmark makes the weights itself, on the
device, from the seed, and hands the program the same arrays laid out as
its own tree (the builder does that; nothing is copied).

The stated arithmetic is f32 with the matmul precision ``"highest"``;
``dtype=jnp.bfloat16`` is the precision BELOW the stated one (weights,
activations, softmax and LayerNorm statistics all in bf16), which the
comparison has to tell from the program (``tools/decode_readings.py``).

One layer is one jitted call, so a model of any depth compiles one layer
and the activations of one block of rows are all that lives beside the
weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    d, h = cfg["n_embd"], cfg["n_head"]
    return {"d": d, "h": h, "f": cfg["n_inner"] or 4 * d,
            "v": cfg["vocab_size"], "t": cfg["n_positions"],
            "layers": cfg["n_layer"], "eps": cfg["layer_norm_epsilon"]}


# ------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw_layer(key, d, f, std, proj_std):
    ks = jax.random.split(key, 16)
    n = functools.partial(jax.random.normal, dtype=jnp.float32)
    return {
        "ln1_g": 1.0 + std * n(ks[0], (d,)), "ln1_b": std * n(ks[1], (d,)),
        "wq": std * n(ks[2], (d, d)), "bq": std * n(ks[3], (d,)),
        "wk": std * n(ks[4], (d, d)), "bk": std * n(ks[5], (d,)),
        "wv": std * n(ks[6], (d, d)), "bv": std * n(ks[7], (d,)),
        "wo": proj_std * n(ks[8], (d, d)), "bo": std * n(ks[9], (d,)),
        "ln2_g": 1.0 + std * n(ks[10], (d,)),
        "ln2_b": std * n(ks[11], (d,)),
        # the two MLP matrices and the head are kept (out, in), as a
        # Linear of the program keeps them: handing them over copies
        # nothing
        "w_fc": std * n(ks[12], (f, d)), "b_fc": std * n(ks[13], (f,)),
        "w_proj": proj_std * n(ks[14], (d, f)),
        "b_proj": std * n(ks[15], (d,)),
    }


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw_ends(key, d, v, t, std):
    ks = jax.random.split(key, 6)
    n = functools.partial(jax.random.normal, dtype=jnp.float32)
    return {
        "wte": std * n(ks[0], (v, d)), "wpe": std * n(ks[1], (t, d)),
        "lnf_g": 1.0 + std * n(ks[2], (d,)), "lnf_b": std * n(ks[3], (d,)),
        # the program's head is a Linear of its own with a bias, not the
        # table transposed (the configuration's `assumed.head`)
        "w_head": std * n(ks[4], (v, d)), "b_head": std * n(ks[5], (v,)),
    }


def draw(cfg: dict, seed: int) -> dict:
    """``{"ends": {...}, "layers": [{...}, ...]}``, f32, on the device.
    GPT-2's own draw (every matrix N(0, ``initializer_range``), the two
    residual projections of a block scaled by 1/sqrt(2 n_layer)), except
    that biases and LayerNorm gains and shifts are drawn too (a trained
    model's are not 0 and 1, and a bias left out has to show)."""
    s = sizes(cfg)
    std = float(cfg["initializer_range"])
    proj_std = std / math.sqrt(2.0 * s["layers"])
    key = jax.random.PRNGKey(seed % (2 ** 32))
    keys = jax.random.split(key, s["layers"] + 1)
    return {"ends": _draw_ends(keys[0], s["d"], s["v"], s["t"], std),
            "layers": [_draw_layer(keys[1 + i], s["d"], s["f"], std,
                                   proj_std)
                       for i in range(s["layers"])]}


def parameter_count(cfg: dict) -> int:
    s = sizes(cfg)
    d, f, v, t = s["d"], s["f"], s["v"], s["t"]
    layer = 4 * (d * d + d) + 2 * d * f + f + d + 4 * d
    return s["layers"] * layer + v * d + t * d + 2 * d + d * v + v


# ------------------------------------------------------------ forward
def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(ends, tokens, dtype):
    t = tokens.shape[1]
    return (ends["wte"].astype(dtype)[tokens]
            + ends["wpe"].astype(dtype)[:t][None])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(w, x, h, eps, dtype):
    """One block over ``x`` (rows, T, D) in ``dtype``."""
    w = jax.tree_util.tree_map(lambda a: a.astype(dtype), w)
    rows, t, d = x.shape
    dh = d // h
    with jax.default_matmul_precision("highest"):
        a = _layer_norm(x, w["ln1_g"], w["ln1_b"], eps)

        def heads(y):
            return y.reshape(rows, t, h, dh).transpose(0, 2, 1, 3)

        q = heads(a @ w["wq"] + w["bq"])
        k = heads(a @ w["wk"] + w["bk"])
        v = heads(a @ w["wv"] + w["bv"])
        scores = jnp.einsum("rhqd,rhkd->rhqk", q, k) / math.sqrt(dh)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("rhqk,rhkd->rhqd", p, v)
        o = o.transpose(0, 2, 1, 3).reshape(rows, t, d)
        x = x + o @ w["wo"] + w["bo"]
        a = _layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
        a = _gelu_new(a @ w["w_fc"].T + w["b_fc"])
        return x + a @ w["w_proj"].T + w["b_proj"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _log_probs(ends, x, eps, dtype):
    with jax.default_matmul_precision("highest"):
        e = {k: ends[k].astype(dtype)
             for k in ("lnf_g", "lnf_b", "w_head", "b_head")}
        x = _layer_norm(x, e["lnf_g"], e["lnf_b"], eps)
        logits = x @ e["w_head"].T + e["b_head"]
        return jax.nn.log_softmax(logits.astype(dtype), axis=-1)


def log_probs(cfg: dict, weights: dict, tokens, dtype=jnp.float32):
    """``tokens`` (rows, T) int32 -> log-probabilities (rows, T, V) in
    ``dtype``: position ``i`` gives the distribution of token ``i + 1``.
    Rows are padded on the right by the caller; a causal model's
    positions left of the padding do not see it."""
    s = sizes(cfg)
    x = _embed(weights["ends"], tokens, dtype)
    for w in weights["layers"]:
        x = _layer(w, x, s["h"], s["eps"], dtype)
    return _log_probs(weights["ends"], x, s["eps"], dtype)


@jax.jit
def _gaps(lp_ref, lp_pick, tokens_next):
    """Per position: how far the reference's log-probability of the
    token that FOLLOWS lies below the reference's best (the served
    token's gap), and the same for the token that ``lp_pick`` puts first
    (the gap of another arithmetic's choice)."""
    best = jnp.max(lp_ref, axis=-1)
    served = jnp.take_along_axis(lp_ref, tokens_next[..., None],
                                 axis=-1)[..., 0]
    pick = jnp.argmax(lp_pick, axis=-1)
    picked = jnp.take_along_axis(lp_ref, pick[..., None], axis=-1)[..., 0]
    return best - served, best - picked


def served_gaps(cfg: dict, weights: dict, tokens, lower=None):
    """For rows of prompt + answer (rows, T): at every position ``i`` the
    gap of token ``i + 1`` under the f32 reference, and, where ``lower``
    names a dtype, the gap of the token the reference computed in that
    dtype puts first there.  Both (rows, T - 1), f32, on the host."""
    import numpy as np
    lp = log_probs(cfg, weights, tokens)[:, :-1].astype(jnp.float32)
    lp_low = lp if lower is None else log_probs(
        cfg, weights, tokens, lower)[:, :-1].astype(jnp.float32)
    served, picked = _gaps(lp, lp_low, jnp.asarray(tokens)[:, 1:])
    return np.asarray(served), (None if lower is None
                                else np.asarray(picked))
