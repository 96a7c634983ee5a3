"""Builder for ``ling-3.0-flash-vl-ep32``: the configuration file → the
product's model (one chip's share of an expert-parallel job, layers
from ``deployment.first_layer`` on), criterion, synthetic records, the
plain reference's step, the counters and the operation counts.

The file carries the published keys.  Those that ``reduced`` lists
count what THIS chip holds; ``published`` gives the model's own counts
and ``deployment`` how many chips share a layer's experts and the
vocabulary's rows.  The product's model is told the whole model and its
share, and works the held counts out itself: this builder checks that
they are the file's.  Criterion, records and the verdict on the
counters are granite's builder's."""

from __future__ import annotations

from bigdl_tpu.models import ling_flash
from bigdl_tpu.models.ling_flash import layer_kinds

from benchmarks import lib

_lm = lib.load_module("builders", "granite_moe_hybrid")
criterion = _lm.criterion
make_samples = _lm.make_samples
counters_correct = _lm.counters_correct
_qwen = lib.load_module("builders", "qwen3_next")
whole_config = _qwen.whole_config
expert_share = _qwen.expert_share

# what the TPU compiler expands itself and names itself (hlo_scopes.py):
# ragged-dot is used by the expert layers' grouped products and nowhere
# else in this model
COMPILER_OPS = {"ragged-dot": "bigdl.moe.experts"}


def build_model(cfg: dict):
    tr, d = cfg["train"], cfg["deployment"]
    return ling_flash(whole_config(cfg), expert_share(cfg),
                      vocab_share=(d["vocab_index"], d["vocab_ways"]),
                      first_layer=d["first_layer"], q_block=tr["q_block"],
                      row_factor=tr["row_factor"])


def reference_step(cfg: dict, compute_dtype, state_dtype=None):
    """The plain reference's jitted SGD step ``(params, ids, targets,
    lr) -> (loss, params - lr * grad)``, which carries the expert bias
    from one call to the next itself.  For ``tools/precision_reading.py``
    only, to read what the precision below the stated one gives:
    ``state_dtype``, the dtype of what the stated arithmetic keeps in f32
    (norms, softmax, router, ``g``, ``beta``, unit length, the delta rule
    and its state, the gates; criterion and update stay f32)."""
    ref = lib.load_module("references", "ling_flash")
    if state_dtype is not None:
        ref.f32 = state_dtype
    return ref.make_sgd_step(whole_config(cfg), expert_share(cfg),
                             cfg["deployment"]["first_layer"], compute_dtype)


def counters(cfg: dict, model, state, tokens: int, steps: int) -> dict:
    """The expert layers' running totals (model state) after ``steps``
    steps of ``tokens`` tokens, summed over the layers, beside the rows
    they had (``moe_counters``, which the ``moe.*`` readers take), the
    largest expert bias, and what a step's delta rule needs
    (``kda_counts``)."""
    by_layer = model.expert_counts(state)
    rows = model.expert_layers()[0][1].experts.n_rows(tokens)   # R, a layer
    records = tokens // cfg["train"]["seq_len"]
    return {"moe_counters": {
        "layers": len(by_layer), "steps": steps,
        "rows_held": sum(c["rows_held"] for c in by_layer),
        "rows_overflow": sum(c["rows_overflow"] for c in by_layer),
        "rows_held_by_layer": [c["rows_held"] for c in by_layer],
        "rows_overflow_by_layer": [c["rows_overflow"] for c in by_layer],
        "rows_by_expert_by_layer": [c["rows_by_expert"] for c in by_layer],
        "bias_abs_max_by_layer": [c["bias_abs_max"] for c in by_layer],
        "rows": rows * len(by_layer) * steps,
        "flops_per_row": expert_flops_per_row(cfg)},
        "kda_counts": kda_counts(cfg, records)}


def expert_flops_per_row(cfg: dict) -> float:
    """Forward + backward of one assignment through one expert: a
    hidden x 2*width and a width x hidden product, 2 operations a
    multiply-accumulate, backward twice the forward."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 3.0 * 2.0 * (d * 2 * f + f * d)


def kinds(cfg: dict) -> list:
    """``"kda"`` or ``"mla"`` for each layer the cut holds."""
    return layer_kinds(cfg, cfg["deployment"]["first_layer"])


def kda_counts(cfg: dict, records: int = 1) -> dict:
    """What the delta rule with a decay a key channel NEEDS a step,
    forward and backward, in every KDA layer, whatever computes it: per
    token and head the three key_dim x value_dim products of the
    recurrence (``S^T k``, ``k d^T``, ``S^T q``) at 2 operations a
    multiply-accumulate and the decay of the state (``key_dim x
    value_dim`` multiplies), the backward twice the forward; its f32
    inputs (``q``, ``k``, ``g`` a key channel, ``v`` a value channel,
    ``beta`` a head) read and its output written once forward and three
    times that backward."""
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    dv = dk
    tokens = records * cfg["train"]["seq_len"] * kinds(cfg).count("kda")
    flops = 3.0 * (2.0 * 3 * dk * dv + dk * dv) * h
    words = h * (3 * dk + dv + 1) + h * dv
    return {"scan_flops_per_step": flops * tokens,
            "scan_bytes_per_step": (1 + 3) * 4.0 * words * tokens}


def train_flops_per_record(cfg: dict) -> float:
    """Forward + backward of one record on THIS chip's share, counted
    from the sizes: per token the projections of each kind of layer,
    the depthwise convolution, the delta rule as the recurrence needs it
    (``kda_counts``), causal attention over half the sequence on
    average, the dense layer's MLP, the router over all experts, the
    balanced load of the held experts, the shared expert and the held
    rows of the untied head.  2 operations a multiply-accumulate,
    backward twice the forward; the embedding is a lookup and what remat
    recomputes counts nothing."""
    c = cfg
    d, t = c["hidden_size"], c["train"]["seq_len"]
    h, dk = c["num_attention_heads"], c["head_dim"]
    width = h * dk
    kda = d * (5 * width + h) + width * d \
        + c["short_conv_kernel_size"] * 3 * width + 3.5 * dk * dk * h
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    mla = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + d * h \
        + h * dv * d + h * (dn + dr + dv) * (t / 2.0)
    e, k = c["published"]["num_experts"], c["num_experts_per_tok"]
    experts = d * e + k * c["num_experts"] / e \
        * 3 * d * c["moe_intermediate_size"] \
        + 3 * d * c["moe_shared_expert_intermediate_size"]
    dense = 3 * d * c["intermediate_size"]
    first = c["deployment"]["first_layer"]
    macs = sum(kda if kind == "kda" else mla for kind in kinds(c)) \
        + sum(dense if i < c["first_k_dense_replace"] else experts
              for i in range(first, first + c["num_hidden_layers"])) \
        + d * c["vocab_size"]
    return 3.0 * 2.0 * macs * t
