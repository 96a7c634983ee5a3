"""Builder for ``qwen3-next-80b-a3b-ep8``: the configuration file → the
product's model (one chip's share of an expert-parallel job), criterion,
synthetic records, the plain reference's step, the counters and the
operation counts.

The file carries HF's own keys.  Those that ``reduced`` lists count what
THIS chip holds; ``published`` gives the model's own counts and
``deployment`` how many chips share a layer's experts and the
vocabulary's rows.  The product's model is told the whole model and its
share, and works the held counts out itself: this builder checks that
they are the file's.  Criterion, records and the verdict on the
counters are granite's builder's: one language-model job, three
configurations."""

from __future__ import annotations

from bigdl_tpu.models import qwen3_next

from benchmarks import lib

_lm = lib.load_module("builders", "granite_moe_hybrid")
criterion = _lm.criterion
make_samples = _lm.make_samples
counters_correct = _lm.counters_correct

# what the TPU compiler expands itself and names itself (hlo_scopes.py):
# ragged-dot is used by the expert layer's grouped products and nowhere
# else in this model
COMPILER_OPS = {"ragged-dot": "bigdl.moe.experts"}

# the key that counts something cut by the deployment -> its ways
CUT = {"num_experts": "expert_ways", "vocab_size": "vocab_ways"}


def whole_config(cfg: dict) -> dict:
    """HF's config of the whole model, cut in depth only."""
    whole = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    d = cfg["deployment"]
    for key, ways in CUT.items():
        n = d[ways]
        if cfg["published"][key] != cfg[key] * n:
            raise lib.BenchFailure(
                f"{key}: {cfg[key]} held x {n} ways is not the published "
                f"{cfg['published'][key]}")
        whole[key] = cfg["published"][key]
    return whole


def expert_share(cfg: dict):
    d = cfg["deployment"]
    return d["expert_index"], d["expert_ways"]


def build_model(cfg: dict):
    tr, d = cfg["train"], cfg["deployment"]
    return qwen3_next(whole_config(cfg), expert_share(cfg),
                      vocab_share=(d["vocab_index"], d["vocab_ways"]),
                      q_block=tr["q_block"], row_factor=tr["row_factor"])


def reference_step(cfg: dict, compute_dtype, state_dtype=None):
    """The plain reference's jitted, donating SGD step
    ``(params, ids, targets, lr) -> (loss, params - lr * grad)``.  For
    ``tools/precision_reading.py`` only, to read what the precision
    below the stated one gives: ``state_dtype``, the dtype of what the
    stated arithmetic keeps in f32 (norms, softmax, router, ``g``,
    ``beta``, unit length, the delta rule, the gates; criterion and
    update stay f32)."""
    ref = lib.load_module("references", "qwen3_next")
    if state_dtype is not None:
        ref.f32 = state_dtype
    return ref.make_sgd_step(whole_config(cfg), expert_share(cfg),
                             compute_dtype)


def counters(cfg: dict, model, state, tokens: int, steps: int) -> dict:
    """The expert layers' running totals (model state) after ``steps``
    steps of ``tokens`` tokens, summed over the layers, beside the rows
    they had (``moe_counters``, which the ``moe.*`` readers take), and
    what a step's delta rule needs (``gdn_counts``)."""
    by_layer = model.expert_counts(state)
    rows = model.layers[0].experts.n_rows(tokens)     # R, a layer
    records = tokens // cfg["train"]["seq_len"]
    return {"moe_counters": {
        "layers": len(by_layer), "steps": steps,
        "rows_held": sum(c["rows_held"] for c in by_layer),
        "rows_overflow": sum(c["rows_overflow"] for c in by_layer),
        "rows_held_by_layer": [c["rows_held"] for c in by_layer],
        "rows_overflow_by_layer": [c["rows_overflow"] for c in by_layer],
        "rows_by_expert_by_layer": [c["rows_by_expert"] for c in by_layer],
        "rows": rows * len(by_layer) * steps,
        "flops_per_row": expert_flops_per_row(cfg)},
        "gdn_counts": gdn_counts(cfg, records)}


def expert_flops_per_row(cfg: dict) -> float:
    """Forward + backward of one assignment through one expert: a
    hidden x 2*width and a width x hidden product, 2 operations a
    multiply-accumulate, backward twice the forward."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 3.0 * 2.0 * (d * 2 * f + f * d)


def linear_layers(cfg: dict) -> int:
    every = cfg["full_attention_interval"]
    return sum((i + 1) % every != 0 for i in range(cfg["num_hidden_layers"]))


def gdn_counts(cfg: dict, records: int = 1) -> dict:
    """What the gated delta rule NEEDS a step, forward and backward, in
    every linear-attention layer, whatever computes it: per token and
    value head the three key_dim x value_dim products of the recurrence
    (``S^T k``, ``k d^T``, ``S^T q``) at 2 operations a multiply-
    accumulate, the backward twice the forward; its f32 inputs (``q``
    and ``k`` once a KEY head, ``v``, ``g`` and ``beta`` a value head)
    read and its output written once forward and three times that
    backward."""
    c = cfg
    hk, h, dk, dv = (c["linear_num_key_heads"], c["linear_num_value_heads"],
                     c["linear_key_head_dim"], c["linear_value_head_dim"])
    tokens = records * c["train"]["seq_len"] * linear_layers(c)
    flops = 3.0 * 2.0 * 3 * dk * dv * h
    words = hk * 2 * dk + h * (dv + 2) + h * dv
    return {"scan_flops_per_step": flops * tokens,
            "scan_bytes_per_step": (1 + 3) * 4.0 * words * tokens}


def train_flops_per_record(cfg: dict) -> float:
    """Forward + backward of one record on THIS chip's share, counted
    from the sizes: per token the projections of each kind of layer,
    the depthwise convolution, the delta rule as the recurrence needs it
    (``gdn_counts``), causal attention over half the sequence on
    average, the router over all experts, the balanced load of the held
    experts, the shared expert and its gate, and the held rows of the
    untied head.  2 operations a multiply-accumulate, backward twice the
    forward; the embedding is a lookup and what remat recomputes counts
    nothing."""
    c = cfg
    d, t = c["hidden_size"], c["train"]["seq_len"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    r = hv // hk
    linear = d * hk * (2 * dk + 2 * r * dv + 2 * r) + hv * dv * d \
        + c["linear_conv_kernel_dim"] * (2 * hk * dk + hv * dv) \
        + 3 * dk * dv * hv
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    attention = d * (2 * hq + 2 * hkv) * dh + hq * dh * d \
        + 2 * hq * dh * (t / 2.0)
    e, k = c["published"]["num_experts"], c["num_experts_per_tok"]
    experts = d * e + k * c["num_experts"] / e \
        * 3 * d * c["moe_intermediate_size"] \
        + 3 * d * c["shared_expert_intermediate_size"] + d
    n_linear = linear_layers(c)
    n_full = c["num_hidden_layers"] - n_linear
    macs = n_linear * linear + n_full * attention \
        + c["num_hidden_layers"] * experts + d * c["vocab_size"]
    return 3.0 * 2.0 * macs * t
