"""What the models built as ONE CHIP'S SHARE of a tensor- and
expert-parallel job have in common (``granite_moe_hybrid``, ``zaya``):
how a count is cut, what a layer's checkpoint keeps, and how the expert
layers' counters are read and put into words.

**What a layer's checkpoint keeps.**  A training step keeps a layer's
input and, by name (``SAVED_IN_LAYER``), the outputs of the expert
block's up-projections: the grouped ``rows W_in`` of the routed experts
(``moe.h``; what lays its rows out goes under the same name: the
experts chosen and each row's assignment and gate) and a shared
expert's ``x W_in`` (``mlp.h``).  Everything else inside the layer is
made a second time by the backward.  ``models/granite_moe_hybrid.py``
says why these and no others; the policy is one algorithm with one
value in use."""

from __future__ import annotations

import functools

import jax

from bigdl_tpu.nn.moe import MLP_H, MOE_H, count_value

SAVED_IN_LAYER = (MOE_H, MLP_H)


def checkpointed(layer, training: bool = False):
    """``layer.apply(params, state, input)`` under the layer's
    checkpoint (module docstring); ``input`` is whatever the layer
    takes, an array or a tuple of them."""
    return jax.checkpoint(
        functools.partial(layer.apply, training=training),
        policy=jax.checkpoint_policies.save_only_these_names(
            *SAVED_IN_LAYER))


def slice_of(total: int, index: int, of: int, what: str):
    """``(lo, hi)``: part ``index`` of ``total`` cut ``of`` ways."""
    if total % of:
        raise ValueError(f"{total} {what} do not split {of} ways")
    step = total // of
    return index * step, (index + 1) * step


def _expert_states(model, state):
    return [state["layers"][str(j)]["experts"]
            for j in range(len(model.layers))]


def expert_counts(model, state) -> list:
    """The host's reading of every layer's expert counters (``model``:
    ``.layers``, each with ``.experts``; ``state``: the model's):
    ``[{"rows_held": n, "rows_overflow": n, "rows_by_expert": [n, ...]},
    ...]``, running totals since ``init``."""
    return [{"rows_held": count_value(s["rows_held"]),
             "rows_overflow": count_value(s["rows_overflow"]),
             "rows_by_expert": [count_value(t)
                                for t in s["rows_by_expert"]]}
            for s in _expert_states(model, state)]


def state_warnings(model, state) -> list:
    """What the expert layers' counters say that a user has to hear;
    read by the optimizers when a run ends, and logged."""
    return [f"layer {j}: {said}"
            for j, (layer, s) in enumerate(zip(model.layers,
                                               _expert_states(model, state)))
            for said in layer.experts.state_warnings(s)]
