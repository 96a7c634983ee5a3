"""``ling_flash``: Ling-3.0-flash language models — five layers of Kimi
delta attention to one of latent attention, and after two leading dense
layers, 512 sigmoid-routed experts in 8 groups beside a shared expert.

No reference analog.  Equations as the configuration's keys state them
(``config`` carries them as published; readings of a key that the
configuration does not spell out are marked *inferred*)::

    h = E[ids]
    per layer i:  h = h + mixer_i(rms(h; w1))
                  h = h + ffn_i(rms(h; w2))
    logits = rms(h; w_f) W_head^T                        (untied head)

``rms`` is the RMS norm with a weight from 1 (eps ``rms_norm_eps``).
``mixer_i`` is ``nn.MultiHeadLatentAttention`` where ``(i + 1) %
layer_group_size == 0`` and ``nn.KimiDeltaAttention`` elsewhere
(*inferred* from ``layer_group_size`` 6: five KDA layers to one MLA;
the catalog's "3 KDA : 1 MLA" describes a sibling).  ``ffn_i`` is a
SwiGLU of ``intermediate_size`` in the first ``first_k_dense_replace``
layers, and after them::

    ffn(x) = sum_e gate_e E_e(x) + E_shared(x)

the routed experts behind ``nn.GroupLimitedSigmoidRouter`` (top
``num_experts_per_tok`` of the ``topk_group`` best of ``n_group``
groups, sigmoid scores, an expert bias for the choice,
``routed_scaling_factor``), every expert and the shared one a SwiGLU of
``moe_intermediate_size``.  After each training step the bias moves by
``nn.moe.BIAS_RATE * sign(mean load - load_e)`` from that step's choice
over all experts (DeepSeek-V3's auxiliary-loss-free rule and rate; the
rate is *assumed*, the configuration gives none).

**The share.**  As ``qwen3_next``'s: ``expert_share=(i, n)`` holds chip
``i``'s ``1/n`` of every expert layer's routed experts, routing over all
of them; attention of both kinds, the router, the shared expert, the
dense layers' MLP and the norms are whole (data parallel);
``vocab_share=(j, m)`` slices the embedding's and the head's rows.
``first_layer``: the published index of the first layer built (the
layers before it lie on other chips as pipeline stages), so that each
layer is of the kind its published index gives.  No collective, nothing
stands in for the absent chips; the bias moves from THIS chip's counts
(the deployment would sum the counts over its data-parallel chips).

Initialisation: every matrix N(0, 0.02) (``initializer_range``; the
routed experts', the router's and the MLPs' are drawn here, in place of
their modules' Xavier), every norm's weight 1, the mixers' own.

Departures: no packed sequences and no attention mask; no dropout; the
vision tower and the multi-token-prediction module are not built (the
configuration has no key that sizes either); a SwiGLU clamp
(``expert_swiglu_limit_list``) is refused where a built layer asks for
one; the routing gates stay f32; one ``jax.checkpoint`` a layer
(``models/share.py``)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.models.share import checkpointed, slice_of
from bigdl_tpu.nn.attention import rms_norm
from bigdl_tpu.nn.moe import count_value
from bigdl_tpu.telemetry.scopes import device_scope

INIT_STD = 0.02
# what the configuration may ask for and this module builds one way only
BUILT = {"tie_word_embeddings": False, "norm_topk_prob": True,
         "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
         "q_lora_rank": None, "use_mla_nope": False, "use_nGPT": False,
         "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False, "no_kda_lora": True, "kda_safe_gate": True,
         "linear_silu": True, "num_kv_heads_for_linear_attn": 0,
         "group_norm_size": 1, "kda_lower_bound": -5,
         "gated_attention_proj_granularity_type": "head_wise"}


def layer_kinds(config: dict, first_layer: int = 0) -> list:
    """``"kda"`` or ``"mla"`` for the ``num_hidden_layers`` layers built
    from published index ``first_layer`` on (module docstring)."""
    every = config["layer_group_size"]
    return ["mla" if (i + 1) % every == 0 else "kda"
            for i in range(first_layer,
                           first_layer + config["num_hidden_layers"])]


def _normal(rng, tree):
    """Every leaf of ``tree`` (shapes) drawn N(0, ``INIT_STD``), f32."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    return treedef.unflatten([INIT_STD * jax.random.normal(
        k, leaf.shape, jnp.float32) for k, leaf in zip(keys, leaves)])


def _buffers(state):
    """A buffer of each leaf: the optimizer donates its state."""
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  state)


class LingFlashLayer(nn.Module):
    """Decoder layer ``index`` (published): mixer and feed-forward block,
    each behind an RMS norm and added to the residual stream."""

    def __init__(self, config: dict, index: int, expert_share=(0, 1), *,
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 name: Optional[str] = None):
        c, (i, n) = config, expert_share
        self.kind = layer_kinds(c, index)[0]
        self.dense = index < c["first_k_dense_replace"]
        super().__init__(name or f"LingFlashLayer[{index}:{self.kind}"
                         f"{'' if self.dense else '+experts'}]")
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            if (c.get(key) or [0] * (index + 1))[index]:
                raise ValueError(f"{key}[{index}]: a SwiGLU clamp is not "
                                 "built")
        D = self.hidden = c["hidden_size"]
        self.eps = c["rms_norm_eps"]
        if self.kind == "kda":
            self.mixer = nn.KimiDeltaAttention(
                D, c["num_attention_heads"], c["head_dim"],
                conv_kernel=c["short_conv_kernel_size"], eps=self.eps)
        else:
            self.mixer = nn.MultiHeadLatentAttention(
                D, c["num_attention_heads"], latent=c["kv_lora_rank"],
                nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
                value_dim=c["v_head_dim"], theta=float(c["rope_theta"]),
                q_block=q_block, eps=self.eps)
        if self.dense:
            self.mlp = nn.GatedMLP(D, c["intermediate_size"])
            return
        E = c["num_experts"]
        self.experts = nn.ExpertParallelMoE(
            D, c["moe_intermediate_size"], E, c["num_experts_per_tok"],
            row_factor=row_factor, held=slice_of(E, i, n, "experts"),
            router=nn.GroupLimitedSigmoidRouter(
                D, E, c["num_experts_per_tok"], n_groups=c["n_group"],
                top_groups=c["topk_group"],
                scale=float(c["routed_scaling_factor"])))
        self.shared = nn.GatedMLP(D, c["moe_shared_expert_intermediate_size"])

    def init(self, rng):
        k_mix, k_ffn, k_sh = jax.random.split(rng, 3)
        D = self.hidden
        params = {"norm1": jnp.ones((D,), jnp.float32),
                  "mixer": self.mixer.init(k_mix)[0],
                  "norm2": jnp.ones((D,), jnp.float32)}
        if self.dense:
            params["mlp"] = _normal(k_ffn, jax.eval_shape(
                self.mlp.init, k_ffn)[0])
            return params, {}
        experts, experts_state = jax.eval_shape(self.experts.init, k_ffn)
        params["experts"] = _normal(k_ffn, experts)
        params["shared"] = _normal(k_sh, jax.eval_shape(
            self.shared.init, k_sh)[0])
        return params, {"experts": _buffers(experts_state)}

    def apply(self, params, state, input, *, training=False, rng=None):
        h, p = input, params
        y, _ = self.mixer.apply(p["mixer"], {},
                                rms_norm(h, p["norm1"], self.eps))
        h = h + y.astype(h.dtype)
        x = rms_norm(h, p["norm2"], self.eps)
        if self.dense:
            with device_scope("mlp"):
                y, _ = self.mlp.apply(p["mlp"], {}, x)
            return h + y.astype(h.dtype), state
        (routed, chosen), experts_state = self.experts.apply(
            p["experts"], state["experts"], x)
        if training:
            with device_scope("moe.route"):
                router = self.experts.router.after_step(
                    state["experts"]["router"], chosen)
            experts_state = dict(experts_state, router=router)
        with device_scope("moe.shared"):
            shared, _ = self.shared.apply(p["shared"], {}, x)
        h = h + (routed.astype(jnp.float32)
                 + shared.astype(jnp.float32)).astype(h.dtype)
        return h, {"experts": experts_state}


class LingFlash(nn.Module):
    """Token ids (N, T) -> logits (N, T, rows held) in f32 (module
    docstring).  ``config``: the published keys of the WHOLE model;
    ``num_hidden_layers`` layers are built, from published index
    ``first_layer`` on."""

    def __init__(self, config: dict, expert_share=(0, 1), *,
                 vocab_share=(0, 1), first_layer: int = 0,
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 name: Optional[str] = None):
        super().__init__(name or "LingFlash")
        for what, (i, n) in (("expert_share", expert_share),
                             ("vocab_share", vocab_share)):
            if not 0 <= i < n:
                raise ValueError(f"{what} {(i, n)}: index outside [0, {n})")
        for key, value in BUILT.items():
            if config.get(key, value) != value:
                raise ValueError(f"{key}={config[key]!r} is not built "
                                 f"(only {value!r})")
        self.config = dict(config)
        self.expert_share = tuple(expert_share)
        self.vocab_rows = slice_of(config["vocab_size"], *vocab_share,
                                   "vocabulary rows")
        self.layers = [LingFlashLayer(config, first_layer + j, expert_share,
                                      q_block=q_block, row_factor=row_factor)
                       for j in range(config["num_hidden_layers"])]

    def init(self, rng):
        D = self.config["hidden_size"]
        lo, hi = self.vocab_rows
        keys = jax.random.split(rng, len(self.layers) + 2)
        layers = [m.init(k) for m, k in zip(self.layers, keys[2:])]
        rows = jax.ShapeDtypeStruct((hi - lo, D), jnp.float32)
        params = {"embed": _normal(keys[0], rows),
                  "layers": {str(j): p for j, (p, _) in enumerate(layers)},
                  "final_norm": jnp.ones((D,), jnp.float32),
                  "head": _normal(keys[1], rows)}
        return params, {"layers": {str(j): s
                                   for j, (_, s) in enumerate(layers)}}

    def apply(self, params, state, input, *, training=False, rng=None):
        c = self.config
        h = jnp.take(params["embed"], input.astype(jnp.int32), axis=0)
        new_state = {}
        for j, layer in enumerate(self.layers):
            h, new_state[str(j)] = checkpointed(layer, training)(
                params["layers"][str(j)], state["layers"][str(j)], h)
        with device_scope("head"):
            x = rms_norm(h, params["final_norm"], c["rms_norm_eps"])
            logits = jnp.einsum("ntd,vd->ntv", x, params["head"],
                                preferred_element_type=jnp.float32)
        return logits, {"layers": new_state}

    def expert_layers(self) -> list:
        """``[(j, layer)]``: the layers with routed experts, by position."""
        return [(j, m) for j, m in enumerate(self.layers) if not m.dense]

    def expert_counts(self, state) -> list:
        """The host's reading of every expert layer's counters and
        router bias: ``[{"layer": j, "rows_held": n, "rows_overflow": n,
        "rows_by_expert": [n, ...], "bias_abs_max": x}, ...]``."""
        out = []
        for j, _ in self.expert_layers():
            s = state["layers"][str(j)]["experts"]
            out.append({"layer": j,
                        "rows_held": count_value(s["rows_held"]),
                        "rows_overflow": count_value(s["rows_overflow"]),
                        "rows_by_expert": [count_value(t)
                                           for t in s["rows_by_expert"]],
                        "bias_abs_max": float(jnp.max(jnp.abs(
                            s["router"]["bias"])))})
        return out

    def state_warnings(self, state) -> list:
        """Read by the optimizers when a run ends, and logged: dropped
        assignments and a runaway expert bias."""
        said = []
        for j, layer in self.expert_layers():
            s = state["layers"][str(j)]["experts"]
            said += [f"layer {j}: {w}" for w in
                     layer.experts.state_warnings(s)
                     + layer.experts.router.state_warnings(s["router"])]
        return said


def ling_flash(config: dict, expert_share=(0, 1), **kw) -> LingFlash:
    """The model of a Ling-3.0-flash configuration, or chip
    ``expert_share[0]``'s part of it in an ``expert_share[1]``-way
    expert-parallel job."""
    return LingFlash(config, expert_share, **kw)
