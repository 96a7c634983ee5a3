"""The plain reference of ``granitemoehybrid`` (HF
``GraniteMoeHybridForCausalLM``; the product's model is
``bigdl_tpu/models/granite_moe_hybrid.py``): ``jax.numpy`` only, no
kernel, no sorting, no chunked scan, nothing imported from the product.

- the state-space layer is the RECURRENCE itself, one step at a time
  (``lax.scan``): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``;
- the experts are a loop over the experts held, each run on EVERY token
  and weighted by that token's gate for it, 0 where it was not chosen;
- attention is the full score matrix, a block of queries at a time,
  keys and values repeated for the query heads that share them.

It reads the product's parameter tree (weights stored (in, out);
``in_proj`` columns ``[z | x | B | C | dt]``) and is given the same
share: how many heads, experts and vocabulary rows are held is read
from the shapes, which experts from ``share``.

Departures from "float32, highest precision", each forced and noted:
(1) the arithmetic follows the parameters' dtype, so that on the chip it
can run in the cell's stated arithmetic — bf16 products over f32 master
parameters, with norms, softmax, router, recurrence, criterion and
update in f32 (``loss_fn`` casts; the contract of
``benchmarks/reference.py`` restated, not imported).  On the CPU the
tests hand it f32 parameters under
``jax.default_matmul_precision("highest")``.  (2) To fit 8,192 steps of
recurrence and ten layers on one chip, a layer and every 256 steps of
the recurrence are a ``jax.checkpoint``: the same numbers, recomputed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
SCAN_SEGMENT = 256
QUERY_BLOCK = 512


def rms(x, w, eps):
    x32 = x.astype(f32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(f32)).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gated_mlp(x, w_in, w_out):
    h = x @ w_in
    half = h.shape[-1] // 2
    return (silu(h[..., :half]) * h[..., half:]) @ w_out


# ------------------------------------------------------------ attention
def attention(config, p, x):
    """NoPE causal grouped-query attention of the heads held (read from
    the shapes).  ``x``: (N, T, D)."""
    N, T, D = x.shape
    dh = D // config["num_attention_heads"]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    n_kv = p["wk"].shape[1] // dh
    q = (x @ p["wq"]).reshape(N, T, n_kv * group, dh)
    k = (x @ p["wk"]).reshape(N, T, n_kv, dh)
    v = (x @ p["wv"]).reshape(N, T, n_kv, dh)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    outs = []
    for lo in range(0, T, QUERY_BLOCK):
        qb = q[:, lo:lo + QUERY_BLOCK]
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k).astype(f32) \
            * config["attention_multiplier"]
        qpos = lo + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qpos, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", w, v))
    o = jnp.concatenate(outs, axis=1).reshape(N, T, n_kv * group * dh)
    return o @ p["wo"]


# ---------------------------------------------------------------- mamba
def recurrence(x, dt, A, B, C, D):
    """``x``: (N, T, H, P); ``dt``: (N, T, H); ``A``, ``D``: (H,);
    ``B``, ``C``: (N, T, G, S).  All f32.  Step by step."""
    N, T, H, P = x.shape
    G, S = B.shape[2], B.shape[3]
    heads_of = jnp.repeat(jnp.arange(G), H // G)    # head -> its group

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp                   # (N,H,P) (N,H) (N,G,S)
        B_h, C_h = B_t[:, heads_of], C_t[:, heads_of]       # (N, H, S)
        decay = jnp.exp(dt_t * A)[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] \
            * B_h[:, :, None, :]
        y_t = jnp.sum(state * C_h[:, :, None, :], axis=-1) \
            + D[:, None] * x_t
        return state, y_t

    seg = SCAN_SEGMENT if T % SCAN_SEGMENT == 0 else T

    @jax.checkpoint
    def segment(state, inp):
        return jax.lax.scan(step, state, inp)

    def split(a):                                   # time first, in segments
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((T // seg, seg) + a.shape[1:])

    _, y = jax.lax.scan(segment, jnp.zeros((N, H, P, S), f32),
                        (split(x), split(dt), split(B), split(C)))
    return jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1)


def mamba_mixer(config, p, u):
    """The Mamba-2 mixer of the heads held (read from the shapes).
    ``u``: (N, T, D)."""
    N, T, _ = u.shape
    P, S = config["mamba_d_head"], config["mamba_d_state"]
    G, K = config["mamba_n_groups"], config["mamba_d_conv"]
    H = p["A_log"].shape[0]
    d_in, d_bc = H * P, G * S
    zxbcdt = u @ p["in_proj"]
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in:2 * d_in + 2 * d_bc]
    dt = zxbcdt[..., 2 * d_in + 2 * d_bc:]
    # depthwise causal conv, as the equation is written
    shifted = [jnp.pad(xBC, ((0, 0), (K - 1 - k, 0), (0, 0)))[:, :T]
               for k in range(K)]
    conv = sum(s * p["conv_w"][k] for k, s in enumerate(shifted))
    if "conv_b" in p:
        conv = conv + p["conv_b"]
    xBC = silu(conv)
    x = xBC[..., :d_in].astype(f32).reshape(N, T, H, P)
    B = xBC[..., d_in:d_in + d_bc].astype(f32).reshape(N, T, G, S)
    C = xBC[..., d_in + d_bc:].astype(f32).reshape(N, T, G, S)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    y = recurrence(x, dt, -jnp.exp(p["A_log"].astype(f32)), B, C,
                   p["D"].astype(f32)).reshape(N, T, d_in)
    # gate first, then the norm over d_inner (norm_before_gate false)
    g = y * silu(z.astype(f32))
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                     + config["rms_norm_eps"])
    return (g * p["norm_w"].astype(f32)).astype(u.dtype) @ p["out_proj"]


# -------------------------------------------------------------- experts
def routed_experts(config, p, x, first_expert: int):
    """What the held experts (``first_expert`` and the following, as many
    as ``p`` holds) add for tokens ``x`` (N, T, D): every one of them on
    every token, times the token's gate for it or 0."""
    N, T, D = x.shape
    x = x.reshape(N * T, D)
    logits = jnp.dot(x.astype(f32), p["router"].astype(f32), precision=_HI)
    values, chosen = jax.lax.top_k(logits, config["num_experts_per_tok"])
    gates = jax.nn.softmax(values, axis=-1)
    out = jnp.zeros((N * T, D), f32)
    for j in range(p["w_in"].shape[0]):
        weight = jnp.sum(jnp.where(chosen == first_expert + j, gates, 0.0),
                         axis=-1)
        y = gated_mlp(x, p["w_in"][j], p["w_out"][j])
        out = out + y.astype(f32) * weight[:, None]
    return out.astype(x.dtype).reshape(N, T, D)


# ------------------------------------------------------------ the model
def layer(config, kind, p, h, first_expert: int):
    mult, eps = config["residual_multiplier"], config["rms_norm_eps"]
    x = rms(h, p["norm1"], eps)
    y = attention(config, p["mixer"], x) if kind == "attention" \
        else mamba_mixer(config, p["mixer"], x)
    h = h + (mult * y).astype(h.dtype)
    x = rms(h, p["norm2"], eps)
    y = routed_experts(config, p["experts"], x, first_expert) \
        + gated_mlp(x, p["shared"]["w_in"], p["shared"]["w_out"])
    return h + (mult * y).astype(h.dtype)


def forward(config, share, params, ids):
    """Token ids (N, T), rows of the held slice -> logits (N, T, rows
    held) in f32, of the share ``(index, of)``."""
    index, of = share
    first_expert = index * (config["num_local_experts"] // of)
    embed = params["embed"]
    h = embed[ids] * jnp.asarray(config["embedding_multiplier"],
                                 embed.dtype)
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for j, kind in enumerate(kinds):
        run = jax.checkpoint(
            lambda p, x, _k=kind: layer(config, _k, p, x, first_expert))
        h = run(params["layers"][str(j)], h)
    x = rms(h, params["final_norm"], config["rms_norm_eps"])
    logits = jnp.einsum("ntd,vd->ntv", x, embed,
                        preferred_element_type=f32)
    return logits / config["logits_scaling"]


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
