"""``qwen3_next``: Qwen3-Next language models — three gated delta-rule
linear-attention layers to one gated full-attention layer, and in every
layer routed experts beside a shared expert behind a sigmoid gate.

No reference analog.  Equations as HF ``Qwen3NextForCausalLM`` states
them (``config`` carries HF's own keys, ``model_type`` ``qwen3_next``)::

    h = E[ids]
    per layer:  h = h + mixer(rms0(h))
                x = rms0(h)
                h = h + experts(x) + sigmoid(x w_s) shared(x)
    logits = rms0(h) W_head^T                         (untied head)

``rms0`` is the zero-centred RMS norm (``nn.zero_centred_rms_norm``,
weight 0 at the start).  ``mixer`` is ``nn.GatedDeltaNet`` or
``nn.GatedAttention`` by ``layer_types``, which HF derives from
``full_attention_interval``: layer ``i`` is full attention where ``(i +
1) % interval == 0``.  ``experts`` is ``nn.ExpertParallelMoE`` behind
``nn.LinearTopKRouter`` (a softmax over all experts, the top k taken and
renormalised, is a softmax over the k chosen logits), ``shared`` a
``nn.GatedMLP``.

**The share.**  ``expert_share=(i, n)`` builds what chip ``i`` of ``n``
holds of every layer of an expert-parallel job: its ``1/n`` of the
routed experts.  Everything else of a layer is whole on every chip (the
deployment runs attention, the linear-attention layers, the router, the
shared expert and the norms data parallel).  ``vocab_share=(j, m)`` is
the slice of the embedding's and the head's rows.  Every expert layer
computes the part of its output that its experts give; the model adds no
collective and nothing that stands in for the other chips
(``tests/test_qwen3_next.py`` ties the shares to the uncut layer).
Token ids and targets are rows of the held slice.

Initialisation as HF's: every matrix N(0, 0.02) (``initializer_range``;
the routed experts', the router's and the shared expert's are drawn
here, in place of their modules' Xavier), the zero-centred norms'
weights 0, the linear-attention layer's own (``nn/gated_delta.py``).

Departures from HF: no packed sequences and no attention mask (every
record is one document); no dropout; the multi-token-prediction module
is not built (HF's causal-LM forward does not run it); the routing
gates stay f32 where HF casts them to the activations' dtype; one
``jax.checkpoint`` a layer (``models/share.py``)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.models.share import (checkpointed, expert_counts, slice_of,
                                    state_warnings)
from bigdl_tpu.nn.attention import zero_centred_rms_norm
from bigdl_tpu.telemetry.scopes import device_scope

INIT_STD = 0.02       # HF's initializer_range
KINDS = ("linear_attention", "full_attention")
# what HF's config may ask for and this module builds one way only
BUILT = {"tie_word_embeddings": False, "norm_topk_prob": True,
         "decoder_sparse_step": 1, "mlp_only_layers": [],
         "use_sliding_window": False}


def layer_types(config: dict) -> list:
    """HF's ``layer_types`` of a ``qwen3_next`` config, from
    ``full_attention_interval``, for ``num_hidden_layers`` layers."""
    every = config["full_attention_interval"]
    return [KINDS[(i + 1) % every == 0]
            for i in range(config["num_hidden_layers"])]


def _normal(rng, tree):
    """Every leaf of ``tree`` (shapes) drawn N(0, ``INIT_STD``), f32."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    return treedef.unflatten([INIT_STD * jax.random.normal(
        k, leaf.shape, jnp.float32) for k, leaf in zip(keys, leaves)])


class Qwen3NextLayer(nn.Module):
    """One decoder layer: mixer and expert block, each behind a
    zero-centred RMS norm and added to the residual stream."""

    def __init__(self, config: dict, kind: str, expert_share=(0, 1), *,
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 name: Optional[str] = None):
        super().__init__(name or f"Qwen3NextLayer[{kind}]")
        c, (i, n) = config, expert_share
        D = self.hidden = c["hidden_size"]
        self.kind, self.eps = kind, c["rms_norm_eps"]
        if kind == "linear_attention":
            self.mixer = nn.GatedDeltaNet(
                D, c["linear_num_key_heads"], c["linear_num_value_heads"],
                c["linear_key_head_dim"], c["linear_value_head_dim"],
                conv_kernel=c["linear_conv_kernel_dim"], eps=self.eps)
        elif kind == "full_attention":
            dh = c["head_dim"]
            self.mixer = nn.GatedAttention(
                D, c["num_attention_heads"], c["num_key_value_heads"], dh,
                rotary=(float(c["rope_theta"]),
                        int(dh * c["partial_rotary_factor"])),
                q_block=q_block, eps=self.eps)
        else:
            raise ValueError(f"layer type {kind!r}")
        self.experts = nn.ExpertParallelMoE(
            D, c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], row_factor=row_factor,
            held=slice_of(c["num_experts"], i, n, "experts"))
        self.shared = nn.GatedMLP(D, c["shared_expert_intermediate_size"])

    def init(self, rng):
        k_mix, k_exp, k_sh, k_gate = jax.random.split(rng, 4)
        D = self.hidden
        experts, experts_state = jax.eval_shape(self.experts.init, k_exp)
        shared, _ = jax.eval_shape(self.shared.init, k_sh)
        return ({"norm1": jnp.zeros((D,), jnp.float32),
                 "mixer": self.mixer.init(k_mix)[0],
                 "norm2": jnp.zeros((D,), jnp.float32),
                 "experts": _normal(k_exp, experts),
                 "shared": _normal(k_sh, shared),
                 "shared_gate": _normal(k_gate, jax.ShapeDtypeStruct(
                     (D, 1), jnp.float32))},
                # a buffer each: the optimizer donates its state
                {"experts": jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), experts_state)})

    def apply(self, params, state, input, *, training=False, rng=None):
        h, p = input, params
        y, _ = self.mixer.apply(p["mixer"], {},
                                zero_centred_rms_norm(h, p["norm1"],
                                                      self.eps))
        h = h + y.astype(h.dtype)
        x = zero_centred_rms_norm(h, p["norm2"], self.eps)
        routed, experts_state = self.experts.apply(
            p["experts"], state["experts"], x)
        with device_scope("moe.shared"):
            shared, _ = self.shared.apply(p["shared"], {}, x)
            gate = jax.nn.sigmoid((x @ p["shared_gate"]).astype(jnp.float32))
            shared = gate * shared.astype(jnp.float32)
        h = h + (routed.astype(jnp.float32) + shared).astype(h.dtype)
        return h, {"experts": experts_state}


class Qwen3Next(nn.Module):
    """Token ids (N, T) -> logits (N, T, rows held) in f32 (module
    docstring).  ``config``: HF's ``qwen3_next`` keys, of the WHOLE
    model; ``num_hidden_layers`` layers are built."""

    def __init__(self, config: dict, expert_share=(0, 1), *,
                 vocab_share=(0, 1), q_block: Optional[int] = 1024,
                 row_factor: float = 1.5, name: Optional[str] = None):
        super().__init__(name or "Qwen3Next")
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
        self.layers = [Qwen3NextLayer(config, kind, expert_share,
                                      q_block=q_block, row_factor=row_factor)
                       for kind in layer_types(config)]

    def init(self, rng):
        D = self.config["hidden_size"]
        lo, hi = self.vocab_rows
        keys = jax.random.split(rng, len(self.layers) + 2)
        layers = [m.init(k) for m, k in zip(self.layers, keys[2:])]
        rows = jax.ShapeDtypeStruct((hi - lo, D), jnp.float32)
        params = {"embed": _normal(keys[0], rows),
                  "layers": {str(j): p for j, (p, _) in enumerate(layers)},
                  "final_norm": jnp.zeros((D,), jnp.float32),
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
            x = zero_centred_rms_norm(h, params["final_norm"],
                                      c["rms_norm_eps"])
            logits = jnp.einsum("ntd,vd->ntv", x, params["head"],
                                preferred_element_type=jnp.float32)
        return logits, {"layers": new_state}

    def expert_counts(self, state) -> list:
        """The host's reading of every layer's expert counters
        (``share.expert_counts``)."""
        return expert_counts(self, state)

    def state_warnings(self, state) -> list:
        """Read by the optimizers when a run ends, and logged."""
        return state_warnings(self, state)


def qwen3_next(config: dict, expert_share=(0, 1), **kw) -> Qwen3Next:
    """The model of an HF ``qwen3_next`` config, or chip
    ``expert_share[0]``'s part of it in an ``expert_share[1]``-way
    expert-parallel job."""
    return Qwen3Next(config, expert_share, **kw)
