"""The plain reference of ``ling_flash`` (Ling-3.0-flash; the product's
model is ``bigdl_tpu/models/ling_flash.py``): ``jax.numpy`` only, no
kernel, no grouped product, no chunked form, nothing imported from the
product.  The equations are the configuration's (the product's module
docstrings and the configuration's ``assumed`` say which readings are
inferred):

- the delta rule with a decay a key channel runs TOKEN BY TOKEN, the
  recurrence as written (``S = Diag(exp g) S; d = beta (v - S^T k);
  S = S + k d^T; o = S^T q``), one ``lax.scan`` over the sequence;
- the depthwise convolution is an explicit sum of shifted sequences;
- latent attention is the full score matrix against all T keys, a block
  of queries at a time, keys of 192 channels and values of 128;
- routing in the published order: sigmoid scores over ALL experts, the
  bias added for the choice, the groups ranked by the sum of their two
  best, the kept groups' top k, the chosen scores renormalised and
  scaled;
- each held expert is a dense product over EVERY token weighted by that
  token's gate for it, 0 where it was not chosen;
- the expert bias moves after the step by ``rate * sign(mean load -
  load)``, the load counted from a dense one-hot of the choice.

It reads the product's parameter tree (weights stored (in, out)) and is
given the same share: how many experts and vocabulary rows are held is
read from the shapes, WHICH experts from ``expert_share``, which
published layers from ``first_layer``.

Departures from "float32, highest precision", each forced and noted:
(1) the arithmetic follows the parameters' dtype, so that on the chip it
can run in the cell's stated arithmetic — bf16 products over f32 master
parameters, with the norms, the softmax, the router, ``g``, ``beta``, the
unit length, the recurrence, the gates, criterion and update in ``f32``
(``loss_fn`` casts).  On the CPU the tests hand it f32 parameters under
``jax.default_matmul_precision("highest")``.  ``f32`` is a module global:
``tools/precision_reading.py`` sets it to bf16 to read what one
precision below gives.  (2) To fit 8,192 tokens and seven layers on one
chip beside 9.3 GB of parameters and gradients, a layer, a block of
queries, an expert's pass over all tokens and a segment of ``SEGMENT``
steps of the recurrence are each a ``jax.checkpoint`` (the same numbers,
recomputed); the sequence is padded to whole segments with steps of
``g = 0`` and ``beta = 0``, which leave the state as it is.  (3) The
routing gates stay f32.  (4) The step (``make_sgd_step``) carries the
expert bias, state and not a parameter, from one call to the next
itself.  Not built, as in the product: the vision tower, the
multi-token-prediction module, a SwiGLU clamp."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
SEGMENT = 64
BIAS_RATE = 1e-3


def rms(x, w, eps):
    """``x / sqrt(mean(x^2) + eps) * w`` in f32, result in ``x``'s dtype."""
    x32 = x.astype(f32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(f32)).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_in, w_out):
    h = x @ w_in
    half = h.shape[-1] // 2
    return (silu(h[..., :half]) * h[..., half:]) @ w_out


def shifted(x, by: int):
    """``x`` (N, T, C) moved ``by`` positions later, zeros in front."""
    T = x.shape[1]
    return jnp.pad(x, ((0, 0), (by, 0), (0, 0)))[:, :T]


def kinds(config, first_layer):
    every = config["layer_group_size"]
    return ["mla" if (i + 1) % every == 0 else "kda"
            for i in range(first_layer,
                           first_layer + config["num_hidden_layers"])]


# ------------------------------------------------------ the delta rule
def kda_rule(q, k, v, g, beta):
    """Token by token.  ``q``, ``k``, ``g``: (N, T, H, K); ``v``:
    (N, T, H, V); ``beta``: (N, T, H).  Returns ``o`` (N, T, H, V) in
    f32."""
    N, T, H, K = q.shape
    V = v.shape[-1]
    pad = -T % SEGMENT
    steps = [jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) *
                     (a.ndim - 2)) for a in (q, k, v, g, beta)]
    # (segments, SEGMENT, N, H, ...): time first
    steps = [jnp.moveaxis(a, 1, 0).reshape(
        ((T + pad) // SEGMENT, SEGMENT) + a.shape[:1] + a.shape[2:])
        for a in steps]

    def one(S, step):
        q_t, k_t, v_t, g_t, b_t = step
        S = S * jnp.exp(g_t)[..., None]
        remembered = jnp.einsum("nhk,nhkv->nhv", k_t, S, precision=_HI)
        d = b_t[..., None] * (v_t - remembered)
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("nhk,nhkv->nhv", q_t, S, precision=_HI)

    @jax.checkpoint
    def segment(S, seg):
        return jax.lax.scan(one, S, seg)

    _, o = jax.lax.scan(segment, jnp.zeros((N, H, K, V), f32), tuple(steps))
    return jnp.moveaxis(o.reshape((T + pad,) + o.shape[2:]), 0, 1)[:, :T]


def kda(config, p, x):
    """The Kimi delta-attention layer.  ``x``: (N, T, D)."""
    N, T, _ = x.shape
    H, K = config["num_attention_heads"], config["head_dim"]
    C = H * K
    y = x @ p["in_proj"]                  # [q | k | v | a | z | b]
    qkv, a, z, b = (y[..., :3 * C], y[..., 3 * C:4 * C],
                    y[..., 4 * C:5 * C], y[..., 5 * C:])
    taps = p["conv_w"].shape[0]
    # y[t] = sum_j w[j] x[t - (taps - 1) + j]
    qkv = silu(sum(shifted(qkv, taps - 1 - j) * p["conv_w"][j]
                   for j in range(taps)))
    q, k, v = (qkv[..., i * C:(i + 1) * C].reshape(N, T, H, K)
               for i in range(3))
    A = jnp.exp(p["A_log"].astype(f32))[:, None]
    g = float(config["kda_lower_bound"]) * jax.nn.sigmoid(
        A * (a.astype(f32) + p["dt_bias"].astype(f32)).reshape(N, T, H, K))
    beta = jax.nn.sigmoid(b.astype(f32))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1,  # noqa: E731
                                          keepdims=True) + 1e-6)
    o = kda_rule(unit(q.astype(f32)) / math.sqrt(K), unit(k.astype(f32)),
                 v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + config["rms_norm_eps"])
    o = o * p["norm_w"].astype(f32) \
        * jax.nn.sigmoid(z.astype(f32)).reshape(N, T, H, K)
    return o.astype(x.dtype).reshape(N, T, C) @ p["out_proj"]


# ------------------------------------------------------------ attention
def rotate(x, theta, rd):
    """HF's ``apply_rotary_pos_emb`` on the first ``rd`` channels of
    ``x`` (N, T, H, Dh): ``x cos + rotate_half(x) sin``, in f32."""
    T = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x32 = x.astype(f32)
    xr, rest = x32[..., :rd], x32[..., rd:]
    half = jnp.concatenate([-xr[..., rd // 2:], xr[..., :rd // 2]], axis=-1)
    return jnp.concatenate([xr * jnp.cos(ang) + half * jnp.sin(ang), rest],
                           axis=-1).astype(x.dtype)


def mla(config, p, x):
    """The latent-attention layer with its gate a head.  ``x``:
    (N, T, D)."""
    N, T, _ = x.shape
    H, r = config["num_attention_heads"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    theta = float(config["rope_theta"])
    q = (x @ p["wq"]).reshape(N, T, H, dn + dr)
    ckr = x @ p["wkv_a"]
    c = rms(ckr[..., :r], p["kv_norm"], config["rms_norm_eps"])
    kv = (c @ p["wkv_b"]).reshape(N, T, H, dn + dv)
    k_r = rotate(ckr[:, :, None, r:], theta, dr)          # one for all heads
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], theta, dr)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r, (N, T, H, dr))], axis=-1)
    v = kv[..., dn:]

    @jax.checkpoint
    def block(qb, lo):
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k).astype(f32) \
            / math.sqrt(dn + dr)
        qpos = lo + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qpos, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("nhqk,nkhd->nqhd", w, v)

    o = jnp.concatenate([block(q[:, lo:lo + QUERY_BLOCK], lo)
                         for lo in range(0, T, QUERY_BLOCK)], axis=1)
    gate = jax.nn.sigmoid((x @ p["wg"]).astype(f32)).astype(o.dtype)
    return (o * gate[..., None]).reshape(N, T, H * dv) @ p["wo"]


# ------------------------------------------------------- router, experts
def choose(config, scores, bias):
    """The experts (T, k) of sigmoid ``scores`` (T, E) under ``bias``:
    groups ranked by the sum of their two best biased scores, the best
    ``topk_group`` kept, the top k of the kept experts."""
    T, E = scores.shape
    G = config["n_group"]
    c = scores + bias
    two_best = jnp.sort(c.reshape(T, G, E // G), axis=-1)[..., -2:]
    _, kept = jax.lax.top_k(jnp.sum(two_best, axis=-1), config["topk_group"])
    in_kept = jnp.sum(jax.nn.one_hot(kept, G, dtype=jnp.int32), axis=1) > 0
    mask = jnp.repeat(in_kept, E // G, axis=1)
    return jax.lax.top_k(jnp.where(mask, c, -jnp.inf),
                         config["num_experts_per_tok"])[1]


def routed_experts(config, p, bias, x, first_expert: int):
    """What the held experts (``first_expert`` and the following, as
    many as ``p`` holds) add for tokens ``x`` (N, T, D), and the choice
    (N * T, k): every held expert on every token, times the token's gate
    for it or 0."""
    N, T, D = x.shape
    x = x.reshape(N * T, D)
    scores = jax.nn.sigmoid(jnp.dot(x.astype(f32), p["router"].astype(f32),
                                    precision=_HI))
    chosen = choose(config, scores, bias.astype(f32))
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = float(config["routed_scaling_factor"]) * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    held = first_expert + jnp.arange(p["w_in"].shape[0])
    weight = jnp.sum(jnp.where(chosen[None] == held[:, None, None],
                               gates[None], 0.0), axis=-1)   # (held, N*T)

    @jax.checkpoint
    def one(w_in, w_out, w):
        return swiglu(x, w_in, w_out).astype(f32) * w[:, None]

    def add(out, expert):
        return out + one(*expert), None

    out, _ = jax.lax.scan(add, jnp.zeros((N * T, D), f32),
                          (p["w_in"], p["w_out"], weight))
    return out.astype(x.dtype).reshape(N, T, D), chosen


def moved_bias(config, bias, chosen):
    """The bias after a step whose choice was ``chosen`` (T, k)."""
    E = bias.shape[0]
    load = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=(0, 1))
    mean = chosen.size / E
    return bias + BIAS_RATE * jnp.sign(mean - load)


# ------------------------------------------------------------ the model
def layer(config, index, kind, p, bias, h, expert_share):
    """Published layer ``index``; ``bias`` None for a dense layer.
    Returns the new ``h`` and the choice (or None)."""
    i, of = expert_share
    eps = config["rms_norm_eps"]
    mixer = kda if kind == "kda" else mla
    h = h + mixer(config, p["mixer"], rms(h, p["norm1"], eps)).astype(
        h.dtype)
    x = rms(h, p["norm2"], eps)
    if index < config["first_k_dense_replace"]:
        return h + swiglu(x, p["mlp"]["w_in"], p["mlp"]["w_out"]).astype(
            h.dtype), None
    routed, chosen = routed_experts(config, p["experts"], bias, x,
                                    i * (config["num_experts"] // of))
    shared = swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
    return h + (routed.astype(f32) + shared.astype(f32)).astype(h.dtype), \
        chosen


def expert_layers(config, first_layer):
    """Positions (in the cut) of the layers with routed experts."""
    return [j for j in range(config["num_hidden_layers"])
            if first_layer + j >= config["first_k_dense_replace"]]


def initial_bias(config, first_layer):
    return {str(j): jnp.zeros((config["num_experts"],), jnp.float32)
            for j in expert_layers(config, first_layer)}


def forward(config, expert_share, first_layer, params, bias, ids):
    """Token ids (N, T), rows of the held slice -> logits (N, T, rows
    held) in f32, of the share ``(index, of)``, and each expert layer's
    choice ``{position: (N * T, k)}``."""
    h = params["embed"][ids]
    choices = {}
    for j, kind in enumerate(kinds(config, first_layer)):
        run = jax.checkpoint(
            lambda p, b, x, j=j, kind=kind: layer(
                config, first_layer + j, kind, p, b, x, expert_share))
        h, chosen = run(params["layers"][str(j)], bias.get(str(j)), h)
        if chosen is not None:
            choices[str(j)] = chosen
    x = rms(h, params["final_norm"], config["rms_norm_eps"])
    return jnp.einsum("ntd,vd->ntv", x, params["head"],
                      preferred_element_type=f32), choices


def cross_entropy(logits, targets):
    """Mean over all tokens of ``-log softmax(logits)[target]``, f32."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(config, expert_share, first_layer, params, bias, ids, targets,
            compute_dtype=None):
    """``(loss, choices)`` on f32 master ``params``: forward (and,
    through ``jax.grad``, backward) in ``compute_dtype``, criterion in
    f32."""
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    logits, choices = forward(config, expert_share, first_layer, params,
                              bias, ids)
    return cross_entropy(logits, targets), choices


def make_sgd_step(config, expert_share, first_layer, compute_dtype=None):
    """``(params, ids, targets, lr) -> (loss, params - lr * grad)``: one
    jitted step that updates the donated parameters in place and moves
    the expert bias it keeps.  A call on parameters that it did not hand
    back itself is the first step of a new run: the bias starts at 0."""

    def step(params, bias, ids, targets, lr):
        (loss, choices), grads = jax.value_and_grad(
            lambda p: loss_fn(config, expert_share, first_layer, p, bias,
                              ids, targets, compute_dtype),
            has_aux=True)(params)
        bias = {j: moved_bias(config, b, choices[j]) for j, b in bias.items()}
        return loss, jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                            params, grads), bias

    jitted = jax.jit(step, donate_argnums=0)
    kept = {"params": None, "bias": None}

    def run(params, ids, targets, lr):
        if params is not kept["params"]:
            kept["bias"] = initial_bias(config, first_layer)
        loss, kept["params"], kept["bias"] = jitted(params, kept["bias"], ids,
                                                    targets, lr)
        return loss, kept["params"]

    run.kept = kept
    return run
