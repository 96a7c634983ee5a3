"""``granitemoehybrid``: IBM Granite 4.0-H language models — Mamba-2
layers with one NoPE grouped-query attention layer among them, and in
every layer routed experts beside a shared expert.

No reference analog.  Equations as HF ``GraniteMoeHybridForCausalLM``
states them (``config`` carries HF's own keys)::

    h = E[ids] * embedding_multiplier
    per layer:  h = h + residual_multiplier * mixer(rms(h))
                x = rms(h)
                h = h + residual_multiplier * (experts(x) + shared(x))
    logits = rms(h) E^T / logits_scaling            (tied embedding)

``mixer`` is ``nn.Mamba2Mixer`` or ``nn.GroupedQueryAttention`` by
``layer_types``; ``experts`` is ``nn.ExpertParallelMoE``, ``shared`` a
``nn.GatedMLP``.

**The share.**  ``share=(i, n)`` builds what chip ``i`` of ``n`` holds
of every layer of a tensor- and expert-parallel job: its ``1/n`` of the
Mamba heads, of the attention's key/value groups with their query
heads, of the routed experts and of the vocabulary's rows; the router,
the shared expert, the norms and Mamba's B and C projections whole.
Every layer is told its share (``held=``) and computes the part of the
layer's output that its heads and experts give; the model adds no
collective and nothing that stands in for the other chips, so with
``n > 1`` the result is the share's own and not the whole model's
(``tests/test_granite_moe_hybrid.py`` ties the shares to the uncut
layer).  Token ids and targets are rows of the held slice,
``0 <= id < vocab_size / n``.  ``share=(0, 1)`` is the whole model.

Departures from HF: no packed sequences and no attention mask (every
record is one document); no dropout; no ``output_router_logits``
auxiliary loss; one ``jax.checkpoint`` a layer.

**What a layer's checkpoint keeps** (``models/share.py``
``checkpointed``, which ``zaya`` uses too).  A training step keeps a layer's
input and, by name (``SAVED_IN_LAYER``), the outputs of the expert
block's two up-projections: the grouped ``rows W_in`` of the routed
experts (``moe.h``; what lays its rows out goes under the same name:
the experts chosen and each row's token, use and gate, 0.5 MB a layer)
and the shared expert's ``x W_in`` (``mlp.h``).  Everything else inside
the layer is made a second time by the backward.  These two because
each is a large product whose result is small beside the work of making
it again (at granite-4.0-h-small's widths and 8,192 tokens on one chip
of eight: 47 and 50 MB a layer for 2.0 and 1.2 ms), and the backward
reads each as it stands.  The cost is their bytes in every layer until
its backward has run: 1.0 GB at 8,192 tokens and ten layers, linear in
both (the compiled step counts 1.67 GB more: PERF.md, PR 33), so a
share that just fits one chip at 16k tokens without them would not with
them.  Not kept, on purpose: Mamba's ``u in_proj`` (38 MB a layer for
0.7 ms; kept, it put ``mixer.out_proj``'s gradient within 1 % of the
limit the benchmark holds it to: a backward that mixes kept arrays with
a second making of the rest rounds otherwise, layer by layer); the
gathered rows (126 MB a layer for a 0.8 ms gather; with them the step
counted 13.07e9 bytes where 13.0e9 was the line drawn); the experts'
``ys`` (made from the kept ``moe.h`` by one product); anything f32 of
the scan (residuals several times the size of what they save); the
attention layer's projections (one layer in ten).  The policy is fixed:
it is one algorithm with one value in use, and the configuration that
needs another brings the argument and its cell.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.models.share import (checkpointed, expert_counts, slice_of,
                                    state_warnings)
from bigdl_tpu.nn.attention import rms_norm
from bigdl_tpu.telemetry.scopes import device_scope


class GraniteMoeHybridLayer(nn.Module):
    """One decoder layer: mixer and expert block, each behind an RMS
    norm and scaled into the residual stream."""

    def __init__(self, config: dict, kind: str, share=(0, 1),
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 name: Optional[str] = None):
        super().__init__(name or f"GraniteMoeHybridLayer[{kind}]")
        c, (i, n) = config, share
        D = c["hidden_size"]
        self.kind = kind
        self.eps = c["rms_norm_eps"]
        self.residual_multiplier = c["residual_multiplier"]
        if kind == "mamba":
            self.mixer = nn.Mamba2Mixer(
                D, c["mamba_n_heads"], c["mamba_d_head"],
                c["mamba_d_state"], n_groups=c["mamba_n_groups"],
                d_conv=c["mamba_d_conv"], chunk_size=c["mamba_chunk_size"],
                held=slice_of(c["mamba_n_heads"], i, n, "Mamba heads"),
                conv_bias=c["mamba_conv_bias"], eps=self.eps)
        elif kind == "attention":
            self.mixer = nn.GroupedQueryAttention(
                D, c["num_attention_heads"], c["num_key_value_heads"],
                D // c["num_attention_heads"],
                held=slice_of(c["num_key_value_heads"], i, n,
                               "key/value heads"),
                scale=c["attention_multiplier"], q_block=q_block)
        else:
            raise ValueError(f"layer type {kind!r}")
        self.experts = nn.ExpertParallelMoE(
            D, c["intermediate_size"], c["num_local_experts"],
            c["num_experts_per_tok"],
            held=slice_of(c["num_local_experts"], i, n, "experts"),
            row_factor=row_factor)
        self.shared = nn.GatedMLP(D, c["shared_intermediate_size"])

    def init(self, rng):
        k_mix, k_exp, k_sh = jax.random.split(rng, 3)
        D = self.shared.hidden_size
        experts, experts_state = self.experts.init(k_exp)
        return ({"norm1": jnp.ones((D,), jnp.float32),
                 "mixer": self.mixer.init(k_mix)[0],
                 "norm2": jnp.ones((D,), jnp.float32),
                 "experts": experts,
                 "shared": self.shared.init(k_sh)[0]},
                {"experts": experts_state})

    def apply(self, params, state, input, *, training=False, rng=None):
        h, mult = input, self.residual_multiplier
        x = rms_norm(h, params["norm1"], self.eps)
        y, _ = self.mixer.apply(params["mixer"], {}, x)
        h = h + (mult * y).astype(h.dtype)
        x = rms_norm(h, params["norm2"], self.eps)
        routed, experts_state = self.experts.apply(
            params["experts"], state["experts"], x)
        with device_scope("moe.shared"):
            shared, _ = self.shared.apply(params["shared"], {}, x)
        h = h + (mult * (routed + shared)).astype(h.dtype)
        return h, {"experts": experts_state}


class GraniteMoeHybrid(nn.Module):
    """Token ids (N, T) -> logits (N, T, rows held) in f32 (module
    docstring).  ``config``: HF's ``GraniteMoeHybridConfig`` keys, of
    the WHOLE model; the first ``num_hidden_layers`` of ``layer_types``
    are built.  ``embed_std``: the embedding's initial standard
    deviation (the config.json carries none)."""

    def __init__(self, config: dict, share=(0, 1), *,
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 embed_std: float = 0.02,
                 name: Optional[str] = None):
        super().__init__(name or "GraniteMoeHybrid")
        self.embed_std = embed_std
        i, n = share
        if not 0 <= i < n:
            raise ValueError(f"share {share}: index outside [0, {n})")
        self.config, self.share = dict(config), (i, n)
        self.vocab_rows = slice_of(config["vocab_size"], i, n,
                                    "vocabulary rows")
        kinds = config["layer_types"][:config["num_hidden_layers"]]
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError("layer_types is shorter than "
                             "num_hidden_layers")
        self.layers = [GraniteMoeHybridLayer(config, kind, (i, n), q_block,
                                             row_factor) for kind in kinds]

    def init(self, rng):
        c = self.config
        D = c["hidden_size"]
        lo, hi = self.vocab_rows
        keys = jax.random.split(rng, len(self.layers) + 1)
        layers = [m.init(k) for m, k in zip(self.layers, keys[1:])]
        # N(0, embed_std): HF's ``initializer_range`` default.  The
        # table is tied to the head, so nn.LookupTable's N(0, 1) would
        # make every token's own logit hidden_size / logits_scaling
        params = {"embed": self.embed_std * jax.random.normal(
                      keys[0], (hi - lo, D), jnp.float32),
                  "layers": {str(j): p for j, (p, _) in enumerate(layers)},
                  "final_norm": jnp.ones((D,), jnp.float32)}
        return params, {"layers": {str(j): s
                                   for j, (_, s) in enumerate(layers)}}

    def apply(self, params, state, input, *, training=False, rng=None):
        c = self.config
        embed = params["embed"]
        h = jnp.take(embed, input.astype(jnp.int32), axis=0) \
            * jnp.asarray(c["embedding_multiplier"], embed.dtype)
        new_state = {}
        for j, layer in enumerate(self.layers):
            h, new_state[str(j)] = checkpointed(layer, training)(
                params["layers"][str(j)], state["layers"][str(j)], h)
        with device_scope("head"):
            x = rms_norm(h, params["final_norm"], c["rms_norm_eps"])
            logits = jnp.einsum("ntd,vd->ntv", x, embed,
                                preferred_element_type=jnp.float32)
            logits = logits / c["logits_scaling"]
        return logits, {"layers": new_state}

    def expert_counts(self, state) -> list:
        """The host's reading of every layer's expert counters
        (``share.expert_counts``)."""
        return expert_counts(self, state)

    def state_warnings(self, state) -> list:
        """Read by the optimizers when a run ends, and logged."""
        return state_warnings(self, state)


def granite_moe_hybrid(config: dict, share=(0, 1), **kw) -> GraniteMoeHybrid:
    """The model of an HF ``granitemoehybrid`` config, or chip
    ``share[0]``'s part of it in a ``share[1]``-way tensor- and
    expert-parallel job."""
    return GraniteMoeHybrid(config, share, **kw)
