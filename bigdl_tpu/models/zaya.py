"""``zaya``: Zyphra's ZAYA1 language models — in every layer attention in
a compressed latent with convolutional mixing (``nn.
CompressedConvAttention``), then 16 routed experts of which a token takes
ONE, chosen by an MLP router whose state runs from layer to layer
(``nn.MLPRouter``); both sublayers enter the residual stream through
learned scalings.

No reference analog.  The wiring is that of "Compressed Convolutional
Attention" (arXiv:2510.04476) and of the ZAYA1 report
(arXiv:2511.17127); ``config`` carries HF's own keys (``model_type``
``zaya``), which give the shapes::

    h = E[ids]                                              r = 0
    per layer:  h = (s1 * h + c1) + (s2 * cca(rms(h)) + c2)
                x = rms(h)
                y, r = experts((x, r))          r: the router's state
                h = (s3 * h + c3) + (s4 * y + c4)
    logits = rms(h) E^T                                (tied embedding)

``s*`` and ``c*`` are learned vectors of the hidden size, 1 and 0 at the
start.  A layer's input and output are the pair ``(h, r)``.  What the
config.json leaves open and this module settles (the benchmark's
configuration file lists each under ``assumed``): the residual scaling's
form, the router's depth and its depth averaging (``nn/moe.py``), the
temperature's parameterisation and the single padding of the
convolutions (``nn/attention.py``).  Not built: the balancing controller
(``bal`` stays 0) and the report's skip choice of the router.

**The share.**  ``share=(i, n)`` builds what chip ``i`` of ``n`` holds of
every layer of a tensor- and expert-parallel job: its ``1/n`` of the
key/value groups with their query heads (and with them the latent's
channels, the convolutions' groups and the value half those heads
read) and of the routed experts; the router, the norms and the scalings
whole.  ``vocab_share=(j, m)`` (default: ``share``) is the slice of the
tied table's rows: the deployment the benchmark states cuts the rows
further than the layers.  Every layer computes the part of its output
that its heads and experts give; the model adds no collective and
nothing that stands in for the other chips (``tests/test_zaya.py`` ties
the shares to the uncut layer).  Token ids and targets are rows of the
held slice.  ``share=(0, 1)`` is the whole model.

Departures from HF: no packed sequences and no attention mask (every
record is one document); no dropout; one ``jax.checkpoint`` a layer
(``models/share.py``: the grouped ``rows W_in`` and what lays its rows
out are kept by name, as granite's)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.models.share import (checkpointed, expert_counts, slice_of,
                                    state_warnings)
from bigdl_tpu.nn.attention import rms_norm
from bigdl_tpu.telemetry.scopes import device_scope

# the residual scalings of a layer: multipliers start at 1, shifts at 0
_SCALES, _SHIFTS = ("s1", "s2", "s3", "s4"), ("c1", "c2", "c3", "c4")


def rotary_of(config: dict):
    """``(theta, channels a head)`` of HF's ``rope_parameters`` for the
    ``hybrid`` layers."""
    rp = config["rope_parameters"]["hybrid"]
    return (float(rp["rope_theta"]),
            int(config["head_dim"] * rp["partial_rotary_factor"]))


class ZayaLayer(nn.Module):
    """One decoder layer, ``(h, r) -> (h, r)`` (module docstring).
    ``first``: the model's first layer, whose router has no previous
    state to add."""

    def __init__(self, config: dict, share=(0, 1), *, first: bool = False,
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 name: Optional[str] = None):
        super().__init__(name or "ZayaLayer")
        c, (i, n) = config, share
        D = self.hidden = c["hidden_size"]
        self.eps = c["rms_norm_eps"]
        self.attention = nn.CompressedConvAttention(
            D, c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], conv=(c["cca_time0"], c["cca_time1"]),
            rotary=rotary_of(c), q_block=q_block, eps=self.eps,
            held=slice_of(c["num_key_value_heads"], i, n,
                          "key/value heads"))
        self.experts = nn.ExpertParallelMoE(
            D, c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], row_factor=row_factor,
            held=slice_of(c["num_experts"], i, n, "experts"),
            router=nn.MLPRouter(D, c["num_experts"],
                                c["num_experts_per_tok"],
                                c["router_hidden_size"], first=first,
                                eps=self.eps))

    def init(self, rng):
        k_att, k_exp = jax.random.split(rng)
        D = self.hidden
        experts, experts_state = self.experts.init(k_exp)
        params = {"norm1": jnp.ones((D,), jnp.float32),
                  "attention": self.attention.init(k_att)[0],
                  "norm2": jnp.ones((D,), jnp.float32),
                  "experts": experts}
        # an array each: the optimizer donates its parameters
        params.update({s: jnp.ones((D,), jnp.float32) for s in _SCALES})
        params.update({c: jnp.zeros((D,), jnp.float32) for c in _SHIFTS})
        return params, {"experts": experts_state}

    def apply(self, params, state, input, *, training=False, rng=None):
        h, r = input
        p = params
        y, _ = self.attention.apply(p["attention"], {},
                                    rms_norm(h, p["norm1"], self.eps))
        h = (p["s1"] * h + p["c1"]) + (p["s2"] * y + p["c2"])
        (y, r), experts_state = self.experts.apply(
            p["experts"], state["experts"],
            (rms_norm(h, p["norm2"], self.eps), r))
        h = (p["s3"] * h + p["c3"]) + (p["s4"] * y + p["c4"])
        return (h, r), {"experts": experts_state}


class Zaya(nn.Module):
    """Token ids (N, T) -> logits (N, T, rows held) in f32 (module
    docstring).  ``config``: HF's ``zaya`` keys, of the WHOLE model;
    ``num_hidden_layers`` layers are built.  ``embed_std``: the
    embedding's initial standard deviation (the config.json carries
    none)."""

    def __init__(self, config: dict, share=(0, 1), *, vocab_share=None,
                 q_block: Optional[int] = 1024, row_factor: float = 1.5,
                 embed_std: float = 0.02, name: Optional[str] = None):
        super().__init__(name or "Zaya")
        vocab_share = vocab_share or share
        for what, (i, n) in (("share", share), ("vocab_share", vocab_share)):
            if not 0 <= i < n:
                raise ValueError(f"{what} {(i, n)}: index outside [0, {n})")
        self.config, self.share = dict(config), tuple(share)
        self.embed_std = embed_std
        self.vocab_rows = slice_of(config["vocab_size"], *vocab_share,
                                   "vocabulary rows")
        self.layers = [ZayaLayer(config, share, first=j == 0,
                                 q_block=q_block, row_factor=row_factor)
                       for j in range(config["num_hidden_layers"])]

    def init(self, rng):
        D = self.config["hidden_size"]
        lo, hi = self.vocab_rows
        keys = jax.random.split(rng, len(self.layers) + 1)
        layers = [m.init(k) for m, k in zip(self.layers, keys[1:])]
        # N(0, embed_std), as granite's: the table is tied to the head
        params = {"embed": self.embed_std * jax.random.normal(
                      keys[0], (hi - lo, D), jnp.float32),
                  "layers": {str(j): p for j, (p, _) in enumerate(layers)},
                  "final_norm": jnp.ones((D,), jnp.float32)}
        return params, {"layers": {str(j): s
                                   for j, (_, s) in enumerate(layers)}}

    def apply(self, params, state, input, *, training=False, rng=None):
        c = self.config
        embed = params["embed"]
        h = jnp.take(embed, input.astype(jnp.int32), axis=0)
        r = jnp.zeros(h.shape[:2] + (c["router_hidden_size"],), jnp.float32)
        new_state = {}
        for j, layer in enumerate(self.layers):
            (h, r), new_state[str(j)] = checkpointed(layer, training)(
                params["layers"][str(j)], state["layers"][str(j)], (h, r))
        with device_scope("head"):
            x = rms_norm(h, params["final_norm"], c["rms_norm_eps"])
            logits = jnp.einsum("ntd,vd->ntv", x, embed,
                                preferred_element_type=jnp.float32)
        return logits, {"layers": new_state}

    def expert_counts(self, state) -> list:
        """The host's reading of every layer's expert counters
        (``share.expert_counts``)."""
        return expert_counts(self, state)

    def state_warnings(self, state) -> list:
        """Read by the optimizers when a run ends, and logged."""
        return state_warnings(self, state)


def zaya(config: dict, share=(0, 1), **kw) -> Zaya:
    """The model of an HF ``zaya`` config, or chip ``share[0]``'s part
    of it in a ``share[1]``-way tensor- and expert-parallel job."""
    return Zaya(config, share, **kw)
