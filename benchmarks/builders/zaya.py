"""Builder for ``zaya1-8b-share2``: the configuration file → the
product's model (one chip's share), criterion, synthetic records, the
plain reference's step, the counters and the operation counts.

The file carries HF's own keys.  Those that ``reduced`` lists count what
THIS chip holds; ``published`` gives the model's own counts and
``deployment`` how many chips share a layer and how many the tied
table's rows.  The product's model is told the whole model and its
share, and works the held counts out itself: this builder checks that
they are the file's.  Criterion, records and the verdict on the
counters are granite's builder's: one language-model job, two
configurations."""

from __future__ import annotations

from bigdl_tpu.models import zaya

from benchmarks import lib

_lm = lib.load_module("builders", "granite_moe_hybrid")
criterion = _lm.criterion
make_samples = _lm.make_samples
counters_correct = _lm.counters_correct

# what the TPU compiler expands itself and names itself (hlo_scopes.py):
# ragged-dot is used by the expert layer's grouped products and nowhere
# else in this model
COMPILER_OPS = {"ragged-dot": "bigdl.moe.experts"}

# the key that counts something cut by the deployment -> how many ways
LAYER_KEYS = ("num_experts", "num_attention_heads", "num_key_value_heads")


def whole_config(cfg: dict) -> dict:
    """HF's config of the whole model, cut in depth only."""
    whole = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    whole["rope_parameters"] = cfg["rope_parameters"]
    d = cfg["deployment"]
    ways = dict.fromkeys(LAYER_KEYS, d["chips_per_layer"])
    ways["vocab_size"] = d["vocab_ways"]
    for key, n in ways.items():
        if cfg["published"][key] != cfg[key] * n:
            raise lib.BenchFailure(
                f"{key}: {cfg[key]} held x {n} ways is not the published "
                f"{cfg['published'][key]}")
        whole[key] = cfg["published"][key]
    return whole


def share(cfg: dict):
    d = cfg["deployment"]
    return d["share_index"], d["chips_per_layer"]


def build_model(cfg: dict):
    tr, d = cfg["train"], cfg["deployment"]
    return zaya(whole_config(cfg), share(cfg),
                vocab_share=(d["vocab_index"], d["vocab_ways"]),
                q_block=tr["q_block"], row_factor=tr["row_factor"],
                embed_std=tr["embed_std"])


def reference_step(cfg: dict, compute_dtype, state_dtype=None):
    """The plain reference's jitted, donating SGD step
    ``(params, ids, targets, lr) -> (loss, params)``.  For
    ``tools/precision_reading.py`` only, to read what the precision
    below the stated one gives: ``state_dtype``, the dtype of what the
    stated arithmetic keeps in f32 (norms, softmax, router, the
    queries' and keys' unit length and temperature; criterion and
    update stay f32)."""
    ref = lib.load_module("references", "zaya")
    if state_dtype is not None:
        ref.f32 = state_dtype
    return ref.make_sgd_step(whole_config(cfg), share(cfg), compute_dtype)


def counters(cfg: dict, model, state, tokens: int, steps: int) -> dict:
    """The expert layers' running totals (model state) after ``steps``
    steps of ``tokens`` tokens, summed over the layers, beside the rows
    they had (``moe_counters``, which the ``moe.*`` readers take), and
    what a step's attention needs (``cca_counts``)."""
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
        "cca_counts": {
            "attend_flops_per_step": records * attend_flops_per_record(cfg)}}


def expert_flops_per_row(cfg: dict) -> float:
    """Forward + backward of one assignment through one expert: a
    hidden x 2*width and a width x hidden product, 2 operations a
    multiply-accumulate, backward twice the forward."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 3.0 * 2.0 * (d * 2 * f + f * d)


def attend_macs_per_token(cfg: dict) -> float:
    """Scores and weighted values of the held query heads against the
    keys a causal query may see, half the sequence on average."""
    return 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * cfg["train"]["seq_len"] / 2.0


def attend_flops_per_record(cfg: dict) -> float:
    """What ``cca.attend`` NEEDS for one record, forward and backward,
    all layers: no masked-out half of a block, nothing made twice."""
    return 3.0 * 2.0 * attend_macs_per_token(cfg) \
        * cfg["train"]["seq_len"] * cfg["num_hidden_layers"]


def train_flops_per_record(cfg: dict) -> float:
    """Forward + backward of one record on THIS chip's share, counted
    from the sizes: per token and layer the projections into and out of
    the latent, both convolutions, causal attention over half the
    sequence on average, the router's four products over all experts,
    the balanced load of the held experts (one expert a token, the held
    share of them); and the held rows of the tied head.  2 operations a
    multiply-accumulate, backward twice the forward; the embedding is a
    lookup and what remat recomputes counts nothing."""
    c = cfg
    d, dh = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    lq, lk = hq * dh, hkv * dh
    attention = d * (lq + 2 * lk) + lq * d \
        + c["cca_time0"] * (lq + lk) + c["cca_time1"] * (hq + hkv) * dh * dh \
        + attend_macs_per_token(c)
    rr, e = c["router_hidden_size"], c["published"]["num_experts"]
    router = d * rr + 2 * rr * rr + rr * e
    experts = c["num_experts_per_tok"] * c["num_experts"] / e \
        * 3 * d * c["moe_intermediate_size"]
    macs = c["num_hidden_layers"] * (attention + router + experts) \
        + d * c["vocab_size"]
    return 3.0 * 2.0 * macs * c["train"]["seq_len"]
