"""``ling_flash`` and its new layers against the plain reference
(``benchmarks/references/ling_flash.py``: the delta rule token by token,
the convolution as shifted sums, latent attention as full scores, the
group-limited sigmoid routing and its bias as the equations state them,
the experts as a masked dense loop), outputs AND gradients, at small
sizes on the CPU in f32 at the highest matmul precision, on seeded
weights.

Tolerances.  Product and reference compute the same f32 quantities in
another order (a chunk's triangular solve and its blocks of 16 against
the recurrence step by step, a grouped product against a masked loop,
blocked against full softmax): ``RTOL`` 2e-5 of the largest element
compared, as ``tests/test_qwen3_next.py``.  The chunked rule alone reads
1e-7 to 6e-6 of it.  A decay taken a head instead of a channel, a block
split at the wrong step, a gate renormalised otherwise or a group kept
by the wrong score moves the result by 1e-2 or more.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.models import ling_flash
from bigdl_tpu.models.ling_flash import LingFlashLayer, layer_kinds
from bigdl_tpu.nn.gated_delta import unit_length
from bigdl_tpu.nn.kda import chunked_kda_rule, decayed_products
from bigdl_tpu.nn.moe import count_value as count

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ling_flash_reference", os.path.join(ROOT, "benchmarks", "references",
                                         "ling_flash.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

RTOL = 2e-5
SHARES = 32
FIRST = 1

# a whole model small enough for the CPU, built as published layers 1-7
# (KDA with the dense MLP, three KDA, MLA, two KDA, all with experts): 2
# heads of 16, a latent of 16 with keys of 8 + 8 and values of 16, 32
# experts top-4 in 8 groups of 4 of which 4 are kept
CFG = dict(
    hidden_size=32, num_attention_heads=2, head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=6000000, first_k_dense_replace=2, intermediate_size=48,
    num_experts=32, num_experts_per_tok=4, moe_intermediate_size=8,
    moe_shared_expert_intermediate_size=8, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, layer_group_size=6, num_hidden_layers=7,
    vocab_size=64, rms_norm_eps=1e-6)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def key(n):
    return jax.random.PRNGKey(n)


def close(got, want, rtol=RTOL, atol=0.0):
    """Every leaf of ``got`` within ``rtol`` of the largest element of
    its twin in ``want`` (plus ``atol``)."""
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= rtol * scale + atol, \
            (float(jnp.max(jnp.abs(g - w))), scale)


def moved(params, n=7):
    """``params`` with every vector moved off its start (a norm's 1,
    ``dt_bias``), so that a weight read wrongly shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key(n), len(leaves))
    return tree.unflatten([
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for k, a in zip(keys, leaves)])


def _grads(f, args, probe, argnums):
    return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * probe),
                            argnums=argnums))(*args)


def test_layer_kinds_are_five_kda_to_one_mla():
    assert layer_kinds(dict(CFG, num_hidden_layers=12)) == \
        (["kda"] * 5 + ["mla"]) * 2
    # the cut: published layers 1-7
    assert layer_kinds(CFG, FIRST) == ["kda"] * 4 + ["mla", "kda", "kda"]
    assert layer_kinds(CFG, FIRST) == ref.kinds(CFG, FIRST)


# ------------------------------------------------------ the delta rule
def _rule_inputs(decay, T=150):
    """(N, T, H, K, V) = (2, T, 3, 16, 8); ``bound``: every ``g`` at the
    layer's -5 over every chunk, where a split at the chunk's edge would
    need e^320."""
    N, H, K, V = 2, 3, 16, 8
    ks = jax.random.split(key(0), 5)
    g = {"none": jnp.zeros((N, T, H, K)),
         "drawn": -5.0 * jax.nn.sigmoid(
             3.0 * jax.random.normal(ks[4], (N, T, H, K))),
         "bound": jnp.full((N, T, H, K), -5.0)}[decay]
    return (unit_length(jax.random.normal(ks[0], (N, T, H, K))) / 4.0,
            unit_length(jax.random.normal(ks[1], (N, T, H, K))),
            jax.random.normal(ks[2], (N, T, H, V)), g,
            jax.random.uniform(ks[3], (N, T, H)))


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", ["none", "drawn", "bound"])
def test_chunked_rule_against_the_recurrence(chunk, decay):
    """T = 150 is no multiple of 16 or 64.  ``g = 0`` keeps every state
    whole; drawn ``g`` differs channel by channel; at the bound every
    factor of a split at a block's middle reaches e^(+-40), and the
    rule still reads the recurrence in values and in all five inputs'
    gradients, every one finite."""
    args = _rule_inputs(decay)
    probe = jax.random.normal(key(9), args[2].shape)
    rule = lambda *a: chunked_kda_rule(*a, chunk=chunk)  # noqa: E731
    got = jax.jit(rule)(*args)
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    close(got, jax.jit(ref.kda_rule)(*args))
    grads = _grads(rule, args, probe, (0, 1, 2, 3, 4))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    # atol: at the bound the gradient of g is a sum of O(1) terms that
    # cancel to 3e-3 at most; it carries their rounding (8.5e-8 read)
    close(grads, _grads(ref.kda_rule, args, probe, (0, 1, 2, 3, 4)),
          atol=2e-7)


def test_blocks_read_the_decay_channel_by_channel():
    """``decayed_products`` against the sum written out elementwise,
    ``sum_c a_tc exp(G_tc - G_sc) k_sc``, at and under the diagonal,
    with ``G`` falling by up to 5 a step."""
    ks = jax.random.split(key(3), 3)
    a, k = (jax.random.normal(x, (2, 64, 8)) for x in ks[:2])
    G = jnp.cumsum(-5.0 * jax.random.uniform(ks[2], (2, 64, 8)), axis=1)
    want = jnp.einsum("ntc,ntsc,nsc->nts", a, jnp.exp(
        jnp.where(jnp.tril(jnp.ones((64, 64), bool))[None, :, :, None],
                  G[:, :, None] - G[:, None], -jnp.inf)), k)
    close(decayed_products(a, k, G), want)
    # a decay a head: every channel the same, the scalar rule's product
    Gh = jnp.broadcast_to(G[..., :1], G.shape)
    close(decayed_products(a, k, Gh),
          jnp.einsum("ntc,nsc->nts", a, k) * jnp.tril(jnp.exp(
              Gh[..., 0][:, :, None] - Gh[..., 0][:, None])))


def test_padded_steps_leave_the_state_as_it_is():
    q, k, v, g, beta = _rule_inputs("drawn", T=64)
    whole = chunked_kda_rule(q, k, v, g, beta, chunk=64)
    close(chunked_kda_rule(q[:, :50], k[:, :50], v[:, :50], g[:, :50],
                           beta[:, :50], chunk=64), whole[:, :50])


def _kda():
    m = nn.KimiDeltaAttention(32, 2, 16, conv_kernel=4)
    return m, moved(m.init(key(0))[0])


def test_kda_layer_against_reference():
    m, p = _kda()
    x = jax.random.normal(key(1), (2, 40, 32))
    run = lambda p, x: m.apply(p, {}, x)[0]  # noqa: E731
    want = lambda p, x: ref.kda(CFG, p, x)  # noqa: E731
    close(jax.jit(run)(p, x), jax.jit(want)(p, x))
    probe = jax.random.normal(key(9), (2, 40, 32))
    # atol: ``A_log``'s gradient is one number a head, a sum over every
    # step and channel of terms of either sign (as Qwen3-Next's)
    close(_grads(run, (p, x), probe, (0, 1)),
          _grads(want, (p, x), probe, (0, 1)), atol=1e-7)


def test_kda_gate_is_bounded_and_per_channel():
    m, p = _kda()
    a = 50.0 * jax.random.normal(key(2), (3, 32))
    g = m.gate(p, a)
    assert g.shape == (3, 2, 16)
    assert bool(jnp.all((g >= -5.0) & (g <= 0.0)))
    assert float(jnp.min(g)) < -4.9


def test_kda_layer_is_causal():
    """A step reads nothing later than itself, bit for bit, but the
    middle step of its block of 16 (``nn/kda.py``: the split that keeps
    a block's factors within e^(+-40)).  Steps from 73 on changed, past
    the middle (72) of their block [64, 80): every step before reads as
    it did.  From 70 on: steps 64-69 move by rounding alone (a factor
    read at step 72 that cancels), every step before 64 not at all."""
    m, p = _kda()
    x = jax.random.normal(key(1), (1, 80, 32))
    run = jax.jit(lambda x: m.apply(p, {}, x)[0])
    a = run(x)
    b = run(x.at[:, 73:].set(jax.random.normal(key(2), (1, 7, 32))))
    assert bool(jnp.all(a[:, :73] == b[:, :73]))
    assert not bool(jnp.all(a[:, 73:] == b[:, 73:]))
    c = run(x.at[:, 70:].set(jax.random.normal(key(2), (1, 10, 32))))
    assert bool(jnp.all(a[:, :64] == c[:, :64]))
    close(c[:, 64:70], a[:, 64:70], rtol=1e-6)


# ----------------------------------------------------- latent attention
def _mla(q_block):
    m = nn.MultiHeadLatentAttention(32, 2, latent=16, nope_dim=8,
                                    rope_dim=8, value_dim=16, theta=6e6,
                                    q_block=q_block)
    return m, moved(m.init(key(0))[0])


@pytest.mark.parametrize("q_block", [8, None])
def test_mla_against_reference(q_block):
    m, p = _mla(q_block)
    x = jax.random.normal(key(1), (2, 20, 32))
    run = lambda p, x: m.apply(p, {}, x)[0]  # noqa: E731
    want = lambda p, x: ref.mla(CFG, p, x)  # noqa: E731
    close(jax.jit(run)(p, x), jax.jit(want)(p, x))
    probe = jax.random.normal(key(9), (2, 20, 32))
    close(_grads(run, (p, x), probe, (0, 1)),
          _grads(want, (p, x), probe, (0, 1)))


def test_keys_and_values_of_two_widths_take_the_block_loop():
    """Keys of 192 channels and values of 128 (the published widths):
    no flash kernel takes two widths, and ``attention_path`` is handed
    no single dtype for them."""
    from bigdl_tpu.ops import pallas_attention
    q, k, v = (1, 32, 8192, 192), (1, 32, 8192, 192), (1, 32, 8192, 128)
    dtype = jnp.bfloat16 if k == v else None
    assert pallas_attention.attention_path(q, k, dtype, True, None,
                                           1024) == ("xla", "dtype")
    # the XLA path gives each query head its values' width
    qs = jax.random.normal(key(0), (1, 2, 12, 24))
    ks_ = jax.random.normal(key(1), (1, 2, 12, 24))
    vs = jax.random.normal(key(2), (1, 2, 12, 16))
    out = nn.dot_product_attention(qs, ks_, vs, causal=True, q_block=4)
    assert out.shape == (1, 2, 12, 16)
    close(out, nn.dot_product_attention(qs, ks_, vs, causal=True))


# ------------------------------------------------------ router, experts
def _ranked(values):
    """Dense top-k mask by ranks: an entry is taken if fewer than k
    entries beat it (greater, or equal at a lower index)."""
    v = values[..., :, None]
    w = values[..., None, :]
    idx = jnp.arange(values.shape[-1])
    beats = (w > v) | ((w == v) & (idx[None, :] < idx[:, None]))
    return jnp.sum(beats, axis=-1)


def _dense_choice(scores, bias, n_groups, top_groups, k):
    """The choice as a (T, E) mask, from ranks alone (no top_k)."""
    T, E = scores.shape
    c = scores + bias
    grouped = c.reshape(T, n_groups, E // n_groups)
    two = jnp.where(_ranked(grouped) < 2, grouped, 0.0).sum(-1)
    keep = jnp.repeat(_ranked(two) < top_groups, E // n_groups, axis=1)
    return _ranked(jnp.where(keep, c, -jnp.inf)) < k


@pytest.mark.parametrize("tied", [False, True], ids=["drawn", "tied"])
def test_router_against_dense_masks_with_a_bias(tied):
    """512 experts in 8 groups, 4 kept, top 8, a nonzero bias, against a
    choice made from ranks alone.  ``tied``: router columns and bias
    repeat group by group, so every group's two best sum alike for every
    token, and inside the kept groups each score comes four times: the
    LOWEST groups are kept and the lower index wins, as ``lax.top_k``
    breaks ties.  The gates are the unbiased scores of the chosen,
    renormalised, times 2.5."""
    router = nn.GroupLimitedSigmoidRouter(64, 512, 8, n_groups=8,
                                          top_groups=4, scale=2.5)
    w = 25.0 * router.init(key(0))[0]
    bias = 0.05 * jax.random.normal(key(3), (512,))
    if tied:
        w, bias = jnp.tile(w[:, :64], (1, 8)), jnp.tile(bias[:64], 8)
    x = jax.random.normal(key(1), (96, 64))
    gates, experts, handed = router.route(w, {"bias": bias}, x)
    assert bool(jnp.all(handed == experts))
    scores = jax.nn.sigmoid(x @ w)
    got = jnp.zeros((96, 512), bool).at[
        jnp.arange(96)[:, None], experts].set(True)
    assert bool(jnp.all(got == _dense_choice(scores, bias, 8, 4, 8)))
    if tied:
        assert bool(jnp.all(experts < 256))
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    close(gates, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    assert bool(jnp.all(jnp.sort(experts, -1) == jnp.sort(ref.choose(
        dict(n_group=8, topk_group=4, num_experts_per_tok=8), scores,
        bias), -1)))


def test_bias_moves_by_the_sign_of_the_load():
    """Above the mean load an expert's bias falls by the rate, below it
    rises, at it stays; the reference's one-hot count says the same."""
    router = nn.GroupLimitedSigmoidRouter(8, 8, 2, n_groups=2, top_groups=2,
                                          scale=1.0)
    experts = jnp.array([[0, 1], [0, 2], [0, 3], [4, 5], [6, 1]])
    # loads 3 1 ... ; 10 choices over 8 experts: mean 1.25
    new = router.after_step({"bias": jnp.full((8,), 0.5)}, experts)["bias"]
    load = np.bincount(np.asarray(experts).ravel(), minlength=8)
    want = 0.5 + 1e-3 * np.sign(1.25 - load)
    np.testing.assert_allclose(np.asarray(new), want, rtol=0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ref.moved_bias(
        {}, jnp.full((8,), 0.5), experts)), want, rtol=0, atol=1e-7)
    assert router.state_warnings({"bias": jnp.full((8,), 0.5)}) == []
    assert "bias of 1.2" in router.state_warnings(
        {"bias": jnp.full((8,), -1.2)})[0]


def test_thirty_two_expert_shares_add_up_to_the_uncut_block():
    """Every chip routes over all 32 experts with the WHOLE router and
    its bias and computes its own one; the shared expert, which every
    chip computes alike, counts once: together the reference's uncut
    expert block."""
    whole = LingFlashLayer(CFG, 2)
    p, s = whole.init(key(0))
    bias = 0.01 * jax.random.normal(key(4), (32,))
    x = jax.random.normal(key(1), (2, 24, 32))
    routed, chosen = ref.routed_experts(CFG, p["experts"], bias, x, 0)
    want = routed + ref.swiglu(x, p["shared"]["w_in"], p["shared"]["w_out"])
    total = whole.shared.apply(p["shared"], {}, x)[0]
    rows = 0
    for i in range(SHARES):
        part = LingFlashLayer(CFG, 2, (i, SHARES)).experts
        mine = {"router": p["experts"]["router"],
                "w_in": p["experts"]["w_in"][i:i + 1],
                "w_out": p["experts"]["w_out"][i:i + 1]}
        assert jax.tree_util.tree_map(jnp.shape, mine) == \
            jax.tree_util.tree_map(jnp.shape, part.init(key(0))[0])
        state = dict(part.init(key(0))[1], router={"bias": bias})
        (out, handed), new = part.apply(mine, state, x)
        assert bool(jnp.all(handed.reshape(-1, 4) == chosen))
        total, rows = total + out, rows + count(new["rows_held"])
    assert rows == 2 * 24 * 4            # every assignment, once
    close(total, want)


# ------------------------------------------------------- the whole model
def _model(share=(0, 1), vocab_share=(0, 1)):
    m = ling_flash(CFG, share, vocab_share=vocab_share, first_layer=FIRST,
                   q_block=8, row_factor=2.0)
    p, s = m.init(key(0))
    return m, moved(p), s


@pytest.mark.parametrize("share,vocab_share", [
    ((0, 1), (0, 1)), ((3, SHARES), (5, 8))],
    ids=["whole", "experts3of32-rows5of8"])
def test_model_against_reference(share, vocab_share):
    m, p, s = _model(share, vocab_share)
    assert [layer.dense for layer in m.layers] == [True] + [False] * 6
    rows = CFG["vocab_size"] // vocab_share[1]
    ids = jax.random.randint(key(1), (2, 20), 0, rows)
    targets = jax.random.randint(key(2), (2, 20), 0, rows)
    bias = ref.initial_bias(CFG, FIRST)
    logits, new = jax.jit(
        lambda p, v: m.apply(p, s, v, training=True))(p, ids)
    assert logits.shape == (2, 20, rows) and logits.dtype == jnp.float32
    want, choices = jax.jit(lambda p: ref.forward(
        CFG, share, FIRST, p, bias, ids))(p)
    close(logits, want)
    counts = m.expert_counts(new)
    assert [c["layer"] for c in counts] == list(range(1, 7))
    assert all(c["rows_overflow"] == 0 for c in counts)
    assert all(sum(c["rows_by_expert"]) == c["rows_held"] for c in counts)
    # the bias moved in training, as the reference moves it; not in eval
    for j, c in choices.items():
        got = new["layers"][j]["experts"]["router"]["bias"]
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.moved_bias(CFG, bias[j], c)))
        assert float(jnp.max(jnp.abs(got))) == pytest.approx(1e-3)
    _, held = jax.jit(lambda p, v: m.apply(p, s, v))(p, ids)
    assert float(jnp.max(jnp.abs(
        held["layers"]["1"]["experts"]["router"]["bias"]))) == 0.0

    def loss(p):
        return ref.cross_entropy(m.apply(p, s, ids, training=True)[0],
                                 targets)

    grads = jax.jit(jax.grad(loss))(p)
    close(grads, jax.jit(jax.grad(lambda p: ref.loss_fn(
        CFG, share, FIRST, p, bias, ids, targets)[0]))(p), atol=1e-7)
    # every parameter learns, the router's through the gates
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert all(float(jnp.max(jnp.abs(g))) > 0 for _, g in flat), \
        [jax.tree_util.keystr(k) for k, g in flat
         if float(jnp.max(jnp.abs(g))) == 0]


def test_what_is_not_built_is_refused():
    with pytest.raises(ValueError, match="do not split"):
        ling_flash(CFG, (0, 3))
    with pytest.raises(ValueError, match="index outside"):
        ling_flash(CFG, (32, 32))
    with pytest.raises(ValueError, match="tie_word_embeddings=True is not"):
        ling_flash(dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="q_lora_rank=1536 is not"):
        ling_flash(dict(CFG, q_lora_rank=1536))
    with pytest.raises(ValueError, match="kda_lower_bound=-10 is not"):
        ling_flash(dict(CFG, kda_lower_bound=-10))
    clamp = [0] * 42
    clamp[5] = 4
    with pytest.raises(ValueError, match=r"expert_swiglu_limit_list\[5\]"):
        ling_flash(dict(CFG, expert_swiglu_limit_list=clamp),
                   first_layer=FIRST)
    # published layers 35-41 clamp, 1-7 do not
    ling_flash(dict(CFG, expert_swiglu_limit_list=[0] * 35 + [4] * 7),
               first_layer=FIRST)


def test_the_published_share_counts_1168_million_parameters():
    """The benchmark's configuration at its published widths, by
    ``model.init``'s shapes (nothing drawn): the dense layer 110.24 M,
    five KDA expert layers 164.64 M, the MLA expert layer 133.55 M, the
    vocabulary's rows and the final norm 100.60 M."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-vl-ep32.json")) as f:
        cfg = json.load(f)
    whole = dict(cfg, num_experts=cfg["published"]["num_experts"],
                 vocab_size=cfg["published"]["vocab_size"])
    m = ling_flash(whole, (0, 32), vocab_share=(0, 8), first_layer=1)
    params, state = jax.eval_shape(m.init, key(0))
    size = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    layers = [size(params["layers"][str(j)]) for j in range(7)]
    assert [layer.kind for layer in m.layers] == \
        ["kda"] * 4 + ["mla", "kda", "kda"]
    assert layers == [110_240_928] + [164_635_808] * 3 + [133_551_616] \
        + [164_635_808] * 2
    assert size(params) == 1_167_571_904
    e = params["layers"]["1"]["experts"]
    assert e["w_in"].shape == (16, 2560, 1536)
    assert e["router"].shape == (2560, 512)
    assert state["layers"]["1"]["experts"]["router"]["bias"].shape == (512,)
    assert params["layers"]["4"]["mixer"]["wkv_b"].shape == (512, 8192)
    assert params["layers"]["0"]["mixer"]["in_proj"].shape == (2560, 20512)


# --------------------------------------------------- through the optimizer
def _optimizer(model, ds, lr, steps):
    opt = optim.LocalOptimizer(
        model, ds, nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                               size_average=True))
    opt.set_optim_method(optim.SGD(learning_rate=lr)).set_seed(3)
    return opt.set_end_when(optim.max_iteration(steps))


def test_two_sgd_steps_through_the_optimizer_against_reference():
    """The comparison the benchmark's runner makes, at the tiny size in
    f32: ``LocalOptimizer.optimize()`` for two steps against the
    reference's ``make_sgd_step`` on weights drawn from the same key;
    the second step routes under the bias the first one moved."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    share, rows = (1, SHARES), 8
    tokens = np.random.default_rng(0).integers(
        0, rows, (4, 21)).astype(np.int32)
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(2)
    model = ling_flash(CFG, share, vocab_share=(1, 8), first_layer=FIRST,
                       q_block=8, row_factor=2.0)
    _optimizer(model, ds, 0.5, 2).optimize()
    p0, _ = model.init(jax.random.split(key(3))[1])
    step = ref.make_sgd_step(CFG, share, FIRST)
    params = jax.tree_util.tree_map(jnp.copy, p0)
    losses = []
    for b in range(2):
        loss, params = step(params, tokens[2 * b:2 * b + 2, :-1],
                            tokens[2 * b:2 * b + 2, 1:], np.float32(0.5))
        losses.append(float(loss))
    assert abs(losses[0] - np.log(rows)) < 0.1
    change = jax.tree_util.tree_map(lambda a, b: a - b, model._params, p0)
    close(change, jax.tree_util.tree_map(lambda a, b: a - b, params, p0),
          atol=2.5e-7)
    for j, bias in step.kept["bias"].items():
        np.testing.assert_array_equal(
            np.asarray(model._state["layers"][j]["experts"]["router"]
                       ["bias"]), np.asarray(bias))
    # a step on parameters the step did not hand back starts a new run
    fresh = jax.tree_util.tree_map(jnp.copy, p0)
    again, _ = step(fresh, tokens[:2, :-1], tokens[:2, 1:], np.float32(0.5))
    assert float(again) == losses[0]


def test_trains_through_local_optimizer():
    """``LocalOptimizer.optimize()`` trains it as it trains the other
    share-built models: bf16 compute over f32 masters, the counters and
    the bias carried out as model state, the loss falling on a task that
    can be learned."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, (32, 1))
    tokens = ((start + np.arange(17)) % 64).astype(np.int32)  # next = +1
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(4)
    model = ling_flash(CFG, first_layer=FIRST, q_block=8, row_factor=2.0)
    opt = _optimizer(model, ds, 1.0, 40).set_compute_dtype(jnp.bfloat16)
    losses = []

    class Summary:
        def add_train_step(self, step, loss, lr, throughput):
            losses.append(float(loss))

        def add_scalar(self, *a, **k):
            pass

        def trigger_for(self, name):
            return None

    opt.set_train_summary(Summary())
    opt.optimize()
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1
    counts = model.expert_counts(model._state)
    assert all(sum(c["rows_by_expert"]) == c["rows_held"] for c in counts)
    # forty steps of 1e-3 at most: the bias moved, and stays under 1
    assert all(0 < c["bias_abs_max"] <= 0.04 + 1e-6 for c in counts)
    assert model.state_warnings(model._state) == []
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(model._params))
