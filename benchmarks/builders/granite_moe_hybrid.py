"""Builder for ``granite-4.0-h-small-share8``: the configuration file →
the product's model (one chip's share), criterion, synthetic records,
the plain reference's step, and the operation counts.

The file carries HF's own keys.  Those that ``reduced`` lists count what
THIS chip holds; ``published`` gives the model's own counts and
``deployment`` how many chips share a layer.  The product's model is
told the whole model and its share, and works the held counts out
itself: this builder checks that they are the file's."""

from __future__ import annotations

import numpy as np

from bigdl_tpu.models import granite_moe_hybrid

from benchmarks import lib

# what the TPU compiler expands itself and names itself (hlo_scopes.py):
# ragged-dot is used by the expert layer's grouped products and nowhere
# else in this model
COMPILER_OPS = {"ragged-dot": "bigdl.moe.experts"}

SHARED_KEYS = ("num_local_experts", "vocab_size", "mamba_n_heads",
               "num_attention_heads", "num_key_value_heads")


def whole_config(cfg: dict) -> dict:
    """HF's config of the whole model, cut in depth only."""
    whole = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    n = cfg["deployment"]["chips_per_layer"]
    for key in SHARED_KEYS:
        if cfg["published"][key] != cfg[key] * n:
            raise lib.BenchFailure(
                f"{key}: {cfg[key]} held x {n} chips is not the published "
                f"{cfg['published'][key]}")
        whole[key] = cfg["published"][key]
    return whole


def share(cfg: dict):
    d = cfg["deployment"]
    return d["share_index"], d["chips_per_layer"]


def build_model(cfg: dict):
    tr = cfg["train"]
    return granite_moe_hybrid(whole_config(cfg), share(cfg),
                              q_block=tr["q_block"],
                              row_factor=tr["row_factor"],
                              embed_std=tr["embed_std"])


def criterion(cfg: dict):
    from bigdl_tpu import nn
    return nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                       size_average=True)


def make_samples(cfg: dict, seed: int, global_batch: int, n_batches: int):
    """Records of ``seq_len`` token ids uniform over the held rows, the
    target of each the next token of the same drawn document."""
    from bigdl_tpu.dataset import Sample
    rng = np.random.default_rng(seed)
    n, t = global_batch * n_batches, cfg["train"]["seq_len"]
    tokens = rng.integers(0, cfg["vocab_size"], (n, t + 1)).astype(np.int32)
    return [Sample(tokens[i, :-1], tokens[i, 1:]) for i in range(n)]


def reference_step(cfg: dict, compute_dtype, state_dtype=None):
    """The plain reference's jitted, donating SGD step
    ``(params, ids, targets, lr) -> (loss, params)``.  For
    ``tools/precision_reading.py`` only, to read what the precision
    below the stated one gives: ``state_dtype``, the dtype of what the
    stated arithmetic keeps in f32 (norms, softmax, router, recurrence;
    criterion and update stay f32)."""
    ref = lib.load_module("references", "granite_moe_hybrid")
    if state_dtype is not None:
        ref.f32 = state_dtype
    return ref.make_sgd_step(whole_config(cfg), share(cfg), compute_dtype)


def counters(cfg: dict, model, state, tokens: int, steps: int) -> dict:
    """The expert layers' running totals (model state) after ``steps``
    steps of ``tokens`` tokens, summed over the layers, beside the rows
    they had: the observation ``moe_counters``, which the ``moe.*``
    readers take."""
    by_layer = model.expert_counts(state)
    rows = model.layers[0].experts.n_rows(tokens)     # R, a layer
    return {"moe_counters": {
        "layers": len(by_layer), "steps": steps,
        "rows_held": sum(c["rows_held"] for c in by_layer),
        "rows_overflow": sum(c["rows_overflow"] for c in by_layer),
        "rows_held_by_layer": [c["rows_held"] for c in by_layer],
        "rows_overflow_by_layer": [c["rows_overflow"] for c in by_layer],
        "rows": rows * len(by_layer) * steps,
        "flops_per_row": expert_flops_per_row(cfg)}}


def counters_correct(observed: dict) -> bool:
    """Dropless in this run: no assignment found no row."""
    return observed["moe_counters"]["rows_overflow"] == 0


def expert_flops_per_row(cfg: dict) -> float:
    """Forward + backward of one assignment through one expert: a
    hidden x 2*width and a width x hidden product, 2 operations a
    multiply-accumulate, backward twice the forward."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 3.0 * 2.0 * (d * 2 * f + f * d)


def train_flops_per_record(cfg: dict) -> float:
    """Forward + backward of one record on THIS chip's share, counted
    from the sizes (XLA counts a scan's body once): per token the
    projections held, the state-space recurrence as the recurrence needs
    it (update and read of a head_dim x state matrix a head), causal
    attention over half the sequence on average, the router over all
    experts, the balanced load of the held experts, the shared expert
    and the held rows of the tied head.  2 operations a
    multiply-accumulate, backward twice the forward; the embedding is a
    lookup and what remat recomputes counts nothing."""
    c = cfg
    d, t = c["hidden_size"], c["train"]["seq_len"]
    hm, p, s = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    d_in, d_bc = hm * p, c["mamba_n_groups"] * s
    mamba = d * (2 * d_in + 2 * d_bc + hm) + d_in * d + 2 * hm * p * s
    dh = d // c["published"]["num_attention_heads"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    attention = d * (hq + 2 * hkv) * dh + hq * dh * d \
        + 2 * hq * dh * (t / 2.0)
    f, fs = c["intermediate_size"], c["shared_intermediate_size"]
    e, k = c["published"]["num_local_experts"], c["num_experts_per_tok"]
    experts = d * e + k * c["num_local_experts"] / e * (3 * d * f) \
        + 3 * d * fs
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    macs = sum(mamba if kind == "mamba" else attention for kind in kinds) \
        + len(kinds) * experts + d * c["vocab_size"]
    return 3.0 * 2.0 * macs * t
