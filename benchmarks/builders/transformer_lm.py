"""Builder for GPT-2-shaped configurations served by ``DecodeService``:
the configuration file (HF's own keys) → the product's
``transformer_lm``, the benchmark's own weights laid out as the
product's tree, the plain reference's gaps, and the counts of bytes and
operations that the roofline and the mfu are made of.

What the ``decode`` runner asks of a builder: ``build_model``,
``draw_weights``, ``product_params``, ``served_gaps``,
``needed_bytes_per_step``, ``flops_per_token``.
"""

from __future__ import annotations

from benchmarks import lib


def _ref():
    return lib.load_module("references", "transformer_lm")


def build_model(cfg: dict):
    from bigdl_tpu.models.transformer import transformer_lm
    if cfg["activation_function"] != "gelu_new":
        raise lib.BenchFailure("transformer_lm's GELU is the tanh form")
    return transformer_lm(cfg["vocab_size"], cfg["n_embd"], cfg["n_head"],
                          cfg["n_layer"], mlp_dim=cfg["n_inner"],
                          max_len=cfg["n_positions"])


def draw_weights(cfg: dict, seed: int) -> dict:
    """The benchmark's own weights, f32, on the device (the reference's
    layout; :func:`product_params` lays the same arrays out again)."""
    return _ref().draw(cfg, seed)


def product_params(cfg: dict, model, weights: dict) -> dict:
    """The same arrays as ``transformer_lm``'s parameter tree (string
    keys by position in each ``Sequential``), checked leaf by leaf
    against the shapes ``model.init`` would give.  Nothing is copied."""
    import jax
    e, n = weights["ends"], cfg["n_layer"]
    tree = {"0": {"weight": e["wte"]}, "1": {"weight": e["wpe"]}}
    for i, w in enumerate(weights["layers"]):
        attn = {"0": {"weight": w["ln1_g"], "bias": w["ln1_b"]},
                "1": {k: w[k] for k in ("wq", "wk", "wv", "wo",
                                        "bq", "bk", "bv", "bo")}}
        mlp = {"0": {"weight": w["ln2_g"], "bias": w["ln2_b"]},
               "1": {"weight": w["w_fc"], "bias": w["b_fc"]},
               "2": {},
               "3": {"weight": w["w_proj"], "bias": w["b_proj"]}}
        tree[str(2 + i)] = {
            "0": {"0": {"0": attn, "1": {}}, "1": {}},
            "1": {"0": {"0": mlp, "1": {}}, "1": {}}}
    tree[str(2 + n)] = {"weight": e["lnf_g"], "bias": e["lnf_b"]}
    tree[str(3 + n)] = {"weight": e["w_head"], "bias": e["b_head"]}
    tree[str(4 + n)] = {}
    want, _state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat_w = jax.tree_util.tree_leaves_with_path(want)
    flat_t = jax.tree_util.tree_leaves_with_path(tree)
    if ([(p, a.shape, a.dtype) for p, a in flat_w]
            != [(p, a.shape, a.dtype) for p, a in flat_t]):
        raise lib.BenchFailure(
            "the benchmark's weights do not lay out as transformer_lm's "
            "parameter tree: the model's structure has changed")
    return tree


def served_gaps(cfg: dict, weights: dict, tokens, lower=None):
    return _ref().served_gaps(cfg, weights, tokens, lower)


# ---------------------------------------------------------- the counts
def _sizes(cfg: dict):
    d = cfg["n_embd"]
    return d, cfg["n_inner"] or 4 * d, cfg["vocab_size"], cfg["n_layer"]


def matmul_parameters(cfg: dict) -> int:
    """Elements of the matrices every token is multiplied by: four
    attention projections and two MLP matrices a layer, and the head."""
    d, f, v, layers = _sizes(cfg)
    return layers * (4 * d * d + 2 * d * f) + d * v


def needed_bytes_per_step(cfg: dict, slots: int,
                          positions_in_use: float) -> float:
    """Bytes a decode step NEEDS to move, from the sizes and never from
    what an implementation moves: every matrix, bias and LayerNorm
    vector read once for the whole slot batch (f32), one row of each
    embedding table a slot, the keys and values of the positions in use
    (summed over the slots) read once and one new position a slot
    written, and the slots' log-probabilities written."""
    d, f, v, layers = _sizes(cfg)
    item = 4
    vectors = layers * (4 * d + f + d + 4 * d) + 2 * d + v
    kv_position = 2 * layers * d * item            # keys + values
    return float(item * (matmul_parameters(cfg) + vectors)
                 + slots * 2 * d * item
                 + (positions_in_use + slots) * kv_position
                 + slots * v * item)


def flops_per_token(cfg: dict, context: float) -> float:
    """Operations the forward pass NEEDS for one token that attends over
    ``context`` positions (itself included): 2 a multiply-accumulate
    over the matrices, and scores and the weighted sum over the
    context, 2 x 2 x context x n_embd a layer.  Lookups, norms and the
    softmax count nothing."""
    d, _f, _v, layers = _sizes(cfg)
    return 2.0 * matmul_parameters(cfg) + 4.0 * layers * d * context


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt of n tokens, causal: token i attends over i + 1, and
    only the last position needs the head's product (it gives the first
    token of the answer)."""
    n = float(prompt_len)
    d, _f, v, layers = _sizes(cfg)
    return (2.0 * (matmul_parameters(cfg) - d * v) * n + 2.0 * d * v
            + 4.0 * layers * d * n * (n + 1.0) / 2.0)
