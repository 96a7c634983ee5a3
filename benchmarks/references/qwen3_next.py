"""The plain reference of ``qwen3_next`` (Qwen3-Next; the product's model
is ``bigdl_tpu/models/qwen3_next.py``): ``jax.numpy`` only, no kernel,
no grouped product, no chunked form, nothing imported from the product.
The equations are HF ``Qwen3NextForCausalLM``'s:

- the gated delta rule runs TOKEN BY TOKEN, the recurrence as written
  (``S = exp(g) S; d = beta (v - S^T k); S = S + k d^T; o = S^T q``),
  one ``lax.scan`` over the sequence;
- the depthwise convolution is an explicit sum of shifted sequences;
- attention is the full score matrix against all T keys, a block of
  queries at a time, keys and values repeated for the query heads that
  share them;
- routing in the published order: a softmax over ALL experts, the top k
  taken, the k renormalised to sum 1;
- each held expert is a dense product over EVERY token weighted by that
  token's gate for it, 0 where it was not chosen.

It reads the product's parameter tree (weights stored (in, out)) and is
given the same share: how many experts and vocabulary rows are held is
read from the shapes, WHICH experts from ``expert_share``.

Departures from "float32, highest precision", each forced and noted:
(1) the arithmetic follows the parameters' dtype, so that on the chip it
can run in the cell's stated arithmetic — bf16 products over f32 master
parameters, with the norms, the softmax, the router, ``g``, ``beta``,
the unit length, the recurrence, the shared expert's gate, criterion and
update in ``f32`` (``loss_fn`` casts).  On the CPU the tests hand it f32
parameters under ``jax.default_matmul_precision("highest")``.  ``f32`` is
a module global: ``tools/precision_reading.py`` sets it to bf16 to read
what one precision below gives.  (2) To fit 8,192 tokens and four layers
on one chip beside 8.2 GB of parameters and gradients, a layer, a block
of queries, an expert's pass over all tokens and a segment of
``SEGMENT`` steps of the recurrence are each a ``jax.checkpoint`` (the
same numbers, recomputed); the sequence is padded to whole segments
with steps of ``g = 0`` and ``beta = 0``, which leave the state as it
is.  (3) The recurrence's output enters the gated norm in f32 (HF casts
it to the activations' dtype first), and the routing gates stay f32
(HF casts them too), as the product does.  Not built, as in the
product: the multi-token-prediction module."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
SEGMENT = 64


def rms0(x, w, eps):
    """The zero-centred RMS norm: ``x / sqrt(mean(x^2) + eps) * (1 + w)``
    in f32, result in ``x``'s dtype."""
    x32 = x.astype(f32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(f32))).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def shifted(x, by: int):
    """``x`` (N, T, C) moved ``by`` positions later, zeros in front."""
    T = x.shape[1]
    return jnp.pad(x, ((0, 0), (by, 0), (0, 0)))[:, :T]


def layer_types(config):
    every = config["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(config["num_hidden_layers"])]


# ------------------------------------------------------ the delta rule
def delta_rule(q, k, v, g, beta):
    """Token by token.  ``q``, ``k``: (N, T, H, K); ``v``: (N, T, H, V);
    ``g``, ``beta``: (N, T, H).  Returns ``o`` (N, T, H, V) in f32."""
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
        S = S * jnp.exp(g_t)[..., None, None]
        remembered = jnp.einsum("nhk,nhkv->nhv", k_t, S, precision=_HI)
        d = b_t[..., None] * (v_t - remembered)
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("nhk,nhkv->nhv", q_t, S, precision=_HI)

    @jax.checkpoint
    def segment(S, seg):
        return jax.lax.scan(one, S, seg)

    _, o = jax.lax.scan(segment, jnp.zeros((N, H, K, V), f32), tuple(steps))
    return jnp.moveaxis(o.reshape((T + pad,) + o.shape[2:]), 0, 1)[:, :T]


def gated_delta_net(config, p, x):
    """The linear-attention layer.  ``x``: (N, T, D)."""
    N, T, _ = x.shape
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    K, V = config["linear_key_head_dim"], config["linear_value_head_dim"]
    r = Hv // Hk
    qkvz = (x @ p["in_proj_qkvz"]).reshape(N, T, Hk, 2 * K + 2 * r * V)
    ba = (x @ p["in_proj_ba"]).reshape(N, T, Hk, 2 * r)
    mixed = jnp.concatenate([qkvz[..., :K].reshape(N, T, Hk * K),
                             qkvz[..., K:2 * K].reshape(N, T, Hk * K),
                             qkvz[..., 2 * K:2 * K + r * V].reshape(
                                 N, T, Hv * V)], axis=-1)
    z = qkvz[..., 2 * K + r * V:].reshape(N, T, Hv, V)
    b = ba[..., :r].reshape(N, T, Hv)
    a = ba[..., r:].reshape(N, T, Hv)
    taps = p["conv_w"].shape[0]
    # y[t] = sum_j w[j] x[t - (taps - 1) + j]
    mixed = silu(sum(shifted(mixed, taps - 1 - j) * p["conv_w"][j]
                     for j in range(taps)))
    q = mixed[..., :Hk * K].reshape(N, T, Hk, K)
    k = mixed[..., Hk * K:2 * Hk * K].reshape(N, T, Hk, K)
    v = mixed[..., 2 * Hk * K:].reshape(N, T, Hv, V)
    beta = jax.nn.sigmoid(b.astype(f32))
    g = -jnp.exp(p["A_log"].astype(f32)) * jnp.logaddexp(
        a.astype(f32) + p["dt_bias"].astype(f32), 0.0)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                  + 1e-6)
    # value head j reads key head j // r
    q = jnp.repeat(unit(q.astype(f32)), r, axis=2) / math.sqrt(K)
    k = jnp.repeat(unit(k.astype(f32)), r, axis=2)
    o = delta_rule(q, k, v, g, beta)
    # the norm first, then the gate
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + config["rms_norm_eps"])
    o = o * p["norm_w"].astype(f32) * silu(z.astype(f32))
    return o.astype(x.dtype).reshape(N, T, Hv * V) @ p["out_proj"]


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


def attention(config, p, x):
    """The gated full-attention layer.  ``x``: (N, T, D)."""
    N, T, _ = x.shape
    dh, H = config["head_dim"], config["num_attention_heads"]
    Hkv = config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    qg = (x @ p["wq"]).reshape(N, T, H, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:].reshape(N, T, H * dh)
    q = rms0(q, p["q_norm"], eps)
    k = rms0((x @ p["wk"]).reshape(N, T, Hkv, dh), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(N, T, Hkv, dh)
    rd = int(dh * config["partial_rotary_factor"])
    q = rotate(q, float(config["rope_theta"]), rd)
    k = rotate(k, float(config["rope_theta"]), rd)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)

    @jax.checkpoint
    def block(qb, lo):
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k).astype(f32) / math.sqrt(dh)
        qpos = lo + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qpos, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("nhqk,nkhd->nqhd", w, v)

    o = jnp.concatenate([block(q[:, lo:lo + QUERY_BLOCK], lo)
                         for lo in range(0, T, QUERY_BLOCK)], axis=1)
    o = o.reshape(N, T, H * dh)
    o = o * jax.nn.sigmoid(gate.astype(f32)).astype(o.dtype)
    return o @ p["wo"]


# ------------------------------------------------------- router, experts
def swiglu(x, w_in, w_out):
    h = x @ w_in
    half = h.shape[-1] // 2
    return (silu(h[..., :half]) * h[..., half:]) @ w_out


def routed_experts(config, p, x, first_expert: int):
    """What the held experts (``first_expert`` and the following, as
    many as ``p`` holds) add for tokens ``x`` (N, T, D): every one of
    them on every token, times the token's gate for it or 0."""
    N, T, D = x.shape
    x = x.reshape(N * T, D)
    logits = jnp.dot(x.astype(f32), p["router"].astype(f32), precision=_HI)
    prob = jax.nn.softmax(logits, axis=-1)            # over ALL experts
    top, chosen = jax.lax.top_k(prob, config["num_experts_per_tok"])
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
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
    return out.astype(x.dtype).reshape(N, T, D)


def expert_block(config, p, x, first_expert: int):
    """The routed experts held and the gated shared expert."""
    routed = routed_experts(config, p["experts"], x, first_expert)
    shared = swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
    gate = jax.nn.sigmoid((x @ p["shared_gate"]).astype(f32))
    return routed.astype(f32) + gate * shared.astype(f32)


# ------------------------------------------------------------ the model
def layer(config, kind, p, h, expert_share):
    index, of = expert_share
    eps = config["rms_norm_eps"]
    mixer = gated_delta_net if kind == "linear_attention" else attention
    h = h + mixer(config, p["mixer"], rms0(h, p["norm1"], eps)).astype(
        h.dtype)
    x = rms0(h, p["norm2"], eps)
    y = expert_block(config, p, x, index * (config["num_experts"] // of))
    return h + y.astype(h.dtype)


def forward(config, expert_share, params, ids):
    """Token ids (N, T), rows of the held slice -> logits (N, T, rows
    held) in f32, of the share ``(index, of)``."""
    h = params["embed"][ids]
    for j, kind in enumerate(layer_types(config)):
        run = jax.checkpoint(
            lambda p, x, kind=kind: layer(config, kind, p, x, expert_share))
        h = run(params["layers"][str(j)], h)
    x = rms0(h, params["final_norm"], config["rms_norm_eps"])
    return jnp.einsum("ntd,vd->ntv", x, params["head"],
                      preferred_element_type=f32)


def cross_entropy(logits, targets):
    """Mean over all tokens of ``-log softmax(logits)[target]``, f32."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(config, expert_share, params, ids, targets, compute_dtype=None):
    """The training loss on f32 master ``params``: forward (and, through
    ``jax.grad``, backward) in ``compute_dtype``, criterion in f32."""
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    return cross_entropy(forward(config, expert_share, params, ids), targets)


def make_sgd_step(config, expert_share, compute_dtype=None):
    """``(params, ids, targets, lr) -> (loss, params - lr * grad)``: one
    jitted step that updates the donated parameters in place."""

    def step(params, ids, targets, lr):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(config, expert_share, p, ids, targets,
                              compute_dtype))(params)
        return loss, jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                            params, grads)

    return jax.jit(step, donate_argnums=0)
