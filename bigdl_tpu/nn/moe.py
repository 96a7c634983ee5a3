"""Gated MLP and the expert layer of an expert-parallel job.

No reference analog (BigDL predates sparse experts).  Equations as HF
``GraniteMoeHybridMoE`` / ``GraniteMoeHybridMLP`` state them::

    gated(x; W_in, W_out) = (silu(h[:, :F]) * h[:, F:]) W_out,  h = x W_in
    logits = x W_r                       over ALL n_experts, f32
    v, e   = top_k(logits);  g = softmax(v)      over the k selected
    out_t  = sum_k g[t, k] * gated(x_t; expert e[t, k])

**The router is a part the expert layer is given** (``router=``), and
there are two.  :class:`LinearTopKRouter` is the three lines above: one
matrix, and the gates a softmax over the k CHOSEN logits.  With k = 1
that gate is 1 for every token whatever the logits say, so no gradient
reaches the router: a top-1 model cannot train behind it.
:class:`MLPRouter` is ZAYA1's (Zyphra, arXiv:2511.17127): the logits
come from a small MLP on a narrow state that also takes the previous
layer's (the layer hands the state on: input ``(x, r_prev)``, output
``(out, r)``), and the gate is the chosen expert's probability over ALL
experts, which does carry a gradient at k = 1::

    r      = x W_d + b_d  (+ g * r_prev after the first layer)
    z      = W_3 gelu(W_2 gelu(W_1 rms(r) + b_1) + b_2)        all f32
    p      = softmax(z);  e = top_k(p + bal);  g = p[e]

Everything after the choice (``plan``, the row buffer, both grouped
products, combine, the counters) is ONE path for both.

:class:`ExpertParallelMoE` is told which experts it HOLDS
(``held=(lo, hi)``) and computes their part of ``out``: what the
experts on other chips would add is left out (the all-to-all and the
sum over chips are the caller's; on one chip the layer runs without its
exchange, and no code stands in for the absent chips).

How the held experts' product is made, and why.  The T x k assignments
are ranked inside their expert (a running count, no sort), those of the
held experts get the rows ``offset[expert] + rank`` of ONE buffer of
``R`` rows, and a grouped product (``jax.lax.ragged_dot``, which the TPU
compiler lowers to its own grouped-matmul kernel) runs over ALL ``R``
rows: the rows past the load are zeros and belong to the last group.
The plan is inverted ONCE: one scatter writes each assignment's index
into its row, and each row's token, use and gate are read from that
index (one scalar scatter a layer; a scatter a quantity, with JAX's JVP
of the gates' scatter, took five: PERF.md, PR 38).
``R`` is static — ``row_factor`` times the balanced load, rounded up to
``ROW_TILE`` — so the device's work depends on the shapes alone and not
on how the drawn tokens happened to route; the price is padding,
``1 - rows_held / R``.

**Dropless only up to ``row_factor``.**  An assignment that finds no
row below ``R`` reads 0: that token loses that expert's part, silently
as far as the numbers go, so it is COUNTED.  Two counters leave the step
as model state (as BatchNorm's running statistics do), both running
totals: ``rows_held``, the assignments computed, and ``rows_overflow``,
those dropped — 0 in a run that may be believed; ``rows_by_expert``
splits the first by held expert (how uneven the load is).  A total is two int32
words (``count_value``; x64 is off, and one word would wrap after 2e5
steps of 10,240 rows).  ``state_warnings`` says in words what
``rows_overflow`` holds, and the optimizers log it when a run ends.
The default ``row_factor`` 1.5 is set from initial routing at the
published granite-4.0-h-small sizes: a freshly drawn router sends an
expert 0.34 to 1.86 times its balanced share in the deep layers, the 9 of
72 held up to 1.27 times theirs, and 1.25 dropped rows in 1 run of 33
(PERF.md, PR 32).  A trained router may skew more, and no auxiliary
loss balances it here: watch the counter, raise the factor.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.nn.attention import rms_norm
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module
from bigdl_tpu.telemetry.scopes import device_scope

# Names a caller's ``jax.checkpoint`` policy may save
# (``save_only_these_names``); under no such policy a tag is the identity.
# MOE_H names ``h`` AND what lays its rows out: the experts chosen, each
# row's assignment (its token and use are read from it elementwise) and
# each row's gate (a few hundred KB).
# A backward that makes the choice a second time gets another one on
# some tokens (``ExpertParallelMoE.route``) -- with 8,192 tokens on the
# chip the experts' gradients then came out as noise (PERF.md, PR 33).
# One name, so ``h`` is never kept without them
MLP_H = "mlp.h"    # GatedMLP: x W_in
MOE_H = "moe.h"    # ExpertParallelMoE: rows W_in, grouped, and their layout


def gated_act(h):
    """``silu(h[..., :F]) * h[..., F:]`` — HF's ``chunk(2, -1)``."""
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


class GatedMLP(Module):
    """``(silu(x W_in[:, :F]) * x W_in[:, F:]) W_out``, no bias; weights
    stored (in, out).  Granite's shared expert is one of these."""

    def __init__(self, hidden_size: int, width: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.width = hidden_size, width

    def init(self, rng):
        k_in, k_out = jax.random.split(rng)
        D, F = self.hidden_size, self.width
        xav = Xavier()
        return {"w_in": xav.init(k_in, (D, 2 * F), D, 2 * F),
                "w_out": xav.init(k_out, (F, D), F, D)}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        h = checkpoint_name(input @ params["w_in"], MLP_H)
        return gated_act(h) @ params["w_out"], state


ROW_TILE = 256
COUNT_WORD = 1 << 30


def count_add(total, n):
    """``total + n`` for a running total kept as two int32 words
    ``[high, low]``, ``low < 2**30``; ``0 <= n < 2**30``."""
    low = total[1] + n
    return jnp.stack([total[0] + low // COUNT_WORD, low % COUNT_WORD])


def count_value(total) -> int:
    """The host's reading of such a total."""
    high, low = (int(word) for word in total)
    return high * COUNT_WORD + low


def expert_rows(tokens: int, top_k: int, n_held: int, n_experts: int,
                row_factor: float) -> int:
    """``R``: ``row_factor`` times the balanced load of the held
    experts, rounded up to ``ROW_TILE`` (and never under one tile)."""
    balanced = tokens * top_k * n_held / n_experts
    return max(ROW_TILE,
               math.ceil(row_factor * balanced / ROW_TILE) * ROW_TILE)


_HI = jax.lax.Precision.HIGHEST


def _read_at(values, experts):
    """``values`` (T, E) read AT ``experts`` (T, k) by a masked sum: the
    numbers a gather gives, bit for bit.  A backward that keeps the
    choice by name and makes ``x`` a second time then differentiates the
    forward's choice.  Its own top-k differs on some tokens (the second
    ``x`` rounds otherwise in bf16); rows of ``h`` kept from the forward
    would then belong to other tokens."""
    chosen = experts[..., None] == jnp.arange(values.shape[-1])
    return jnp.sum(jnp.where(chosen, values[:, None, :], 0), axis=-1)


class LinearTopKRouter(Module):
    """``logits = x W_r`` over all experts, the ``top_k`` largest chosen,
    gates a softmax over the CHOSEN logits (granite's; module docstring).
    Its parameters are the bare (hidden, n_experts) matrix."""

    def __init__(self, hidden_size: int, n_experts: int, top_k: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.n_experts = hidden_size, n_experts
        self.top_k = top_k

    def init(self, rng):
        D, E = self.hidden_size, self.n_experts
        return Xavier().init(rng, (D, E), D, E), {}

    def route(self, router, state, x):
        """``(gates (T, k) f32, experts (T, k) int32)`` of tokens
        ``x`` (T, D): logits over all experts in f32 at the highest
        precision (a tie broken the other way is another expert)."""
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=_HI)
        _, experts = jax.lax.top_k(logits, self.top_k)
        experts = checkpoint_name(experts, MOE_H)
        return jax.nn.softmax(_read_at(logits, experts), axis=-1), experts


class MLPRouter(Module):
    """ZAYA1's router (module docstring): a down-projection to
    ``width``, the previous layer's state added through a learned gain
    (``first=True``: the model's first layer, which has none to add), an
    RMS norm, two GELU layers of ``width`` and a bias-free read-out over
    all experts; the gate is the chosen expert's probability over ALL of
    them.  All of it f32 at the highest precision.  ``bal`` (state, zeros)
    is added to the probabilities for the CHOICE only: the balancing
    controller that would move it is not built."""

    def __init__(self, hidden_size: int, n_experts: int, top_k: int,
                 width: int, *, first: bool = False, eps: float = 1e-5,
                 name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.n_experts = hidden_size, n_experts
        self.top_k, self.width = top_k, width
        self.first, self.eps = first, eps

    def init(self, rng):
        D, R, E = self.hidden_size, self.width, self.n_experts
        ks = jax.random.split(rng, 4)
        xav = Xavier()
        params = {"wd": xav.init(ks[0], (D, R), D, R),
                  "bd": jnp.zeros((R,), jnp.float32),
                  "norm": jnp.ones((R,), jnp.float32),
                  "w1": xav.init(ks[1], (R, R), R, R),
                  "b1": jnp.zeros((R,), jnp.float32),
                  "w2": xav.init(ks[2], (R, R), R, R),
                  "b2": jnp.zeros((R,), jnp.float32),
                  "w3": xav.init(ks[3], (R, E), R, E)}
        if not self.first:
            params["g"] = jnp.ones((R,), jnp.float32)
        return params, {"bal": jnp.zeros((E,), jnp.float32)}

    def route(self, router, state, x, r_prev):
        """``(gates (T, k) f32, experts (T, k) int32, r (T, width)
        f32)`` of tokens ``x`` (T, D) and the previous layer's state
        ``r_prev`` (T, width; unread by a ``first`` layer)."""
        f32 = jnp.float32
        p = jax.tree_util.tree_map(lambda a: a.astype(f32), router)
        dot = functools.partial(jnp.dot, precision=_HI)
        r = dot(x.astype(f32), p["wd"]) + p["bd"]
        if not self.first:
            r = r + p["g"] * r_prev.astype(f32)
        z = rms_norm(r, p["norm"], self.eps)
        z = jax.nn.gelu(dot(z, p["w1"]) + p["b1"], approximate=False)
        z = jax.nn.gelu(dot(z, p["w2"]) + p["b2"], approximate=False)
        prob = jax.nn.softmax(dot(z, p["w3"]), axis=-1)
        _, experts = jax.lax.top_k(
            prob + jax.lax.stop_gradient(state["bal"]), self.top_k)
        experts = checkpoint_name(experts, MOE_H)
        return _read_at(prob, experts), experts, r


class ExpertParallelMoE(Module):
    """The routed experts one chip holds (module docstring).

    ``n_experts`` experts of width ``expert_width`` in the layer,
    ``top_k`` per token, ``held=(lo, hi)`` the experts here (default:
    all); the buffer has ``expert_rows(...)`` rows for the tokens seen at
    trace time.  ``router``: the module that chooses (default: a
    :class:`LinearTopKRouter`).  Input and output: (N, T, D); with a
    router that carries a state from layer to layer (:class:`MLPRouter`)
    the input is ``(x, r_prev)`` and the output ``(out, r)``."""

    def __init__(self, hidden_size: int, expert_width: int, n_experts: int,
                 top_k: int, *, held=None, row_factor: float = 1.5,
                 router: Optional[Module] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        lo, hi = held if held is not None else (0, n_experts)
        if not 0 <= lo < hi <= n_experts:
            raise ValueError(f"held experts {held} outside "
                             f"[0, {n_experts}]")
        self.hidden_size, self.expert_width = hidden_size, expert_width
        self.n_experts, self.top_k = n_experts, top_k
        self.held = (lo, hi)
        self.n_held = hi - lo
        self.row_factor = row_factor
        self.router = router if router is not None else LinearTopKRouter(
            hidden_size, n_experts, top_k)

    def init(self, rng):
        k_r, k_in, k_out = jax.random.split(rng, 3)
        D, F, n = self.hidden_size, self.expert_width, self.n_held
        xav = Xavier()
        router, router_state = self.router.init(k_r)
        params = {"router": router,
                  "w_in": xav.init(k_in, (n, D, 2 * F), D, 2 * F),
                  "w_out": xav.init(k_out, (n, F, D), F, D)}
        # a buffer each, not one twice: the optimizer donates its state
        return params, {"rows_held": jnp.zeros((2,), jnp.int32),
                        "rows_overflow": jnp.zeros((2,), jnp.int32),
                        "rows_by_expert": jnp.zeros((n, 2), jnp.int32),
                        "router": router_state}

    def n_rows(self, tokens: int) -> int:
        return expert_rows(tokens, self.top_k, self.n_held, self.n_experts,
                           self.row_factor)

    def route(self, router, x):
        """``(gates, experts)`` of tokens ``x`` (T, D) by a router that
        carries nothing from layer to layer."""
        return self.router.route(router, {}, x)

    def plan(self, experts, rows: int):
        """Where each assignment goes.  ``experts``: (T, k).  Returns
        ``row`` (T, k) — the buffer row, ``rows`` for an assignment that
        has none — the groups' ``sizes`` (n_held,) that tile all
        ``rows`` rows, and the two counts of this step."""
        lo, _ = self.held
        local = experts.reshape(-1) - lo                     # (A,)
        mine = local[:, None] == jnp.arange(self.n_held)     # (A, n_held)
        held = mine.any(axis=1)
        counts = jnp.cumsum(mine.astype(jnp.int32), axis=0)
        rank = jnp.sum(jnp.where(mine, counts, 0), axis=1) - 1
        size = counts[-1]                                    # (n_held,)
        start = jnp.cumsum(size) - size
        row = jnp.sum(jnp.where(mine, start, 0), axis=1) + rank
        fits = held & (row < rows)
        row = jnp.where(fits, row, rows)
        n_fit = jnp.sum(fits.astype(jnp.int32))
        # the groups as the product sees them: clipped to the buffer,
        # and the padding rows (zeros) counted to the last group
        end = jnp.minimum(start + size, rows)
        sizes = end - jnp.minimum(start, rows)
        sizes = sizes.at[-1].add(rows - jnp.sum(sizes))
        return (row.reshape(experts.shape), sizes, n_fit,
                jnp.sum(held.astype(jnp.int32)) - n_fit)

    def apply(self, params, state, input, *, training=False, rng=None):
        input, *carried = input if isinstance(input, tuple) else (input,)
        N, T, D = input.shape
        x = input.reshape(N * T, D)
        R = self.n_rows(N * T)
        with device_scope("moe.route"):
            gates, experts, *carried = self.router.route(
                params["router"], state["router"], x,
                *(c.reshape(N * T, -1) for c in carried))
            row, sizes, n_fit, n_over = self.plan(experts, R)
        with device_scope("moe.dispatch"):
            # the plan inverted by ONE index scatter of T*k scalars: each
            # row's assignment, -1 on a row nobody claimed.  Its token and
            # use are read from it elementwise, its gate (below) by a
            # gather of R; then ONE gather of R rows.  An unclaimed row
            # reads token 0 and gate 0, and is zeroed
            flat = row.reshape(-1)
            slot = checkpoint_name(jnp.full((R,), -1, jnp.int32).at[flat].set(
                jnp.arange(flat.size, dtype=jnp.int32), mode="drop"), MOE_H)
            used = slot >= 0
            assignment = jnp.maximum(slot, 0)
            token = assignment // self.top_k
            xs = jnp.where(used[:, None], x[token], 0)
        with device_scope("moe.experts"):
            h = checkpoint_name(
                jax.lax.ragged_dot(xs, params["w_in"], sizes), MOE_H)
            ys = jax.lax.ragged_dot(gated_act(h), params["w_out"], sizes)
        with device_scope("moe.combine"):
            # a token's rows weighted by their gates and added up, in f32.
            # A used row has an assignment of its own (``plan``), so the
            # gather's backward adds each gate's cotangent once, and an
            # unused row's exact 0
            gate = checkpoint_name(jnp.where(
                used, gates.reshape(-1)[assignment], 0.0), MOE_H)
            out = jnp.zeros((N * T, D), jnp.float32).at[token].add(
                ys.astype(jnp.float32) * gate[:, None])
        # the rows each held expert computed: its group, less the padding
        # that ``plan`` counts to the last one
        by_expert = sizes.at[-1].add(n_fit - R)
        new_state = {"rows_held": count_add(state["rows_held"], n_fit),
                     "rows_overflow": count_add(state["rows_overflow"],
                                                n_over),
                     "rows_by_expert": jax.vmap(count_add)(
                         state["rows_by_expert"], by_expert),
                     "router": state["router"]}
        outs = (out.astype(input.dtype).reshape(N, T, D),
                *(c.reshape(N, T, -1) for c in carried))
        return (outs if len(outs) > 1 else outs[0]), new_state

    def state_warnings(self, state) -> list:
        """What the counters say that a user has to hear (host side,
        after a run): the assignments that found no row."""
        dropped = count_value(state["rows_overflow"])
        if not dropped:
            return []
        return [f"{self.name}: {dropped} of "
                f"{dropped + count_value(state['rows_held'])} assignments "
                f"to the held experts found no row and read 0 "
                f"(row_factor {self.row_factor}): the layer was not "
                f"dropless in this run; raise row_factor"]


# the expert bias's update speed (DeepSeek-V3's)
BIAS_RATE = 1e-3


class GroupLimitedSigmoidRouter(Module):
    """Ling-3.0-flash's router (DeepSeek-V3's, arXiv:2412.19437, section
    2.1.2): sigmoid scores over all experts, a bias that only the CHOICE
    reads, the choice limited to the best groups of experts::

        s    = sigmoid(x W_r)                  over ALL experts, f32
        c    = s + bias                        for the choice only
        keep the ``top_groups`` best of ``n_groups`` groups of experts,
             a group ranked by the sum of its two best c
        e    = top_k(c) inside the kept groups
        gate = scale * s[e] / (sum s[e] + 1e-20)

    ``bias`` (state, zeros at the start) is moved OUTSIDE the gradient
    by the auxiliary-loss-free rule, ``bias += BIAS_RATE * sign(mean
    load - load_e)`` from one step's count of the tokens each expert took
    (:meth:`after_step`; DeepSeek-V3's rate, which Ling-3.0-flash's
    configuration does not give).
    The router hands its choice out beside the expert layer's output (the
    ``(out, carried)`` form of :class:`ExpertParallelMoE`): the layer
    that owns the state counts it and calls :meth:`after_step`, as a
    training step ends.  Its parameters are the bare (hidden, n_experts)
    matrix, drawn N(0, 0.02)."""

    def __init__(self, hidden_size: int, n_experts: int, top_k: int, *,
                 n_groups: int, top_groups: int, scale: float,
                 name: Optional[str] = None):
        super().__init__(name)
        if n_experts % n_groups or not 0 < top_groups <= n_groups:
            raise ValueError(f"{n_experts} experts in {n_groups} groups, "
                             f"{top_groups} kept: not a partition")
        if top_k > top_groups * (n_experts // n_groups):
            raise ValueError(f"top {top_k} of {top_groups} groups of "
                             f"{n_experts // n_groups}")
        self.hidden_size, self.n_experts, self.top_k = hidden_size, \
            n_experts, top_k
        self.n_groups, self.top_groups = n_groups, top_groups
        self.scale = scale

    def init(self, rng):
        D, E = self.hidden_size, self.n_experts
        return 0.02 * jax.random.normal(rng, (D, E), jnp.float32), \
            {"bias": jnp.zeros((E,), jnp.float32)}

    def choose(self, scores, bias):
        """The experts (T, k) int32 for sigmoid ``scores`` (T, E) under
        ``bias`` (E,): the kept groups by their two best biased scores,
        then the top k of those groups (a tie goes to the lower index)."""
        T, E = scores.shape
        c = scores + jax.lax.stop_gradient(bias)
        grouped = c.reshape(T, self.n_groups, E // self.n_groups)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)   # (T, G)
        _, kept = jax.lax.top_k(best, self.top_groups)
        keep = jnp.any(kept[..., None] == jnp.arange(self.n_groups), axis=1)
        c = jnp.where(jnp.repeat(keep, E // self.n_groups, axis=1), c,
                      -jnp.inf)
        return jax.lax.top_k(c, self.top_k)[1]

    def route(self, router, state, x):
        """``(gates (T, k) f32, experts (T, k) int32, experts)`` of
        tokens ``x`` (T, D): the scores in f32 at the highest precision
        (a tie broken the other way is another expert); the choice
        twice, the second time to be handed out."""
        scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                        router.astype(jnp.float32),
                                        precision=_HI))
        experts = checkpoint_name(self.choose(scores, state["bias"]), MOE_H)
        chosen = _read_at(scores, experts)
        gates = self.scale * chosen / (jnp.sum(chosen, axis=-1,
                                               keepdims=True) + 1e-20)
        return gates, experts, experts

    def after_step(self, state, experts):
        """The state after one training step whose choice was
        ``experts`` (any shape, ids of all ``n_experts``)."""
        load = jnp.zeros((self.n_experts,), jnp.float32).at[
            experts.reshape(-1)].add(1.0)
        mean = experts.size / self.n_experts
        return {"bias": state["bias"] + BIAS_RATE * jnp.sign(mean - load)}

    def state_warnings(self, state) -> list:
        """A bias that has run to 1 or further, either way: next to
        sigmoid scores in (0, 1) it decides the choice alone."""
        far = float(jnp.max(jnp.abs(state["bias"])))
        if far < 1.0:
            return []
        return [f"{self.name}: an expert bias of {far:.3g}: the bias, "
                f"not the scores, chooses the experts"]
