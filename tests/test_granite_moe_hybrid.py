"""``granitemoehybrid`` and its five layers against the plain reference
(``benchmarks/references/granite_moe_hybrid.py``: the recurrence step by
step, the experts as a masked loop, full attention scores), logits AND
gradients, at small sizes on the CPU in f32 at the highest matmul
precision, on seeded weights.

Tolerances.  Product and reference compute the same f32 quantities in
another order (a chunked scan's matrix products against 8..48 steps of
recurrence, a grouped product against a masked loop, blocked against
full softmax), so they differ by f32 rounding that grows with the
length of the sums: ``RTOL`` 2e-5 of the largest element compared, ten
to a hundred ulps.  A wrong decay, a missed gate, a head read from the
wrong group or an assignment dropped moves the result by 1e-2 or more.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.models import granite_moe_hybrid
from bigdl_tpu.models.granite_moe_hybrid import GraniteMoeHybridLayer
from bigdl_tpu.models.share import checkpointed
from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.nn.moe import COUNT_WORD, count_add, count_value as count

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "granite_reference", os.path.join(
        ROOT, "benchmarks", "references", "granite_moe_hybrid.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

RTOL = 2e-5
SHARES = 8

# a whole model small enough for the CPU, every count a multiple of 8
# so that eight chips can share each layer
CFG = dict(
    attention_multiplier=0.25, embedding_multiplier=12, hidden_size=32,
    intermediate_size=16, layer_types=["mamba", "attention", "mamba"],
    logits_scaling=16, mamba_chunk_size=8, mamba_conv_bias=True,
    mamba_d_conv=4, mamba_d_head=4, mamba_d_state=8, mamba_n_groups=1,
    mamba_n_heads=16, num_attention_heads=16, num_experts_per_tok=4,
    num_hidden_layers=3, num_key_value_heads=8, num_local_experts=16,
    residual_multiplier=0.22, rms_norm_eps=1e-5,
    shared_intermediate_size=24, vocab_size=64)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def key(n):
    return jax.random.PRNGKey(n)


def close(got, want, rtol=RTOL):
    """Every leaf of ``got`` within ``rtol`` of the largest element of
    its twin in ``want``."""
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= rtol * scale, \
            (float(jnp.max(jnp.abs(g - w))), scale)


def both(product, reference, params, x, rtol=RTOL):
    """Outputs and gradients (w.r.t. parameters and input, of a random
    projection of the output) of two functions of ``(params, x)``."""
    out_p, out_r = jax.jit(product)(params, x), jax.jit(reference)(params, x)
    close(out_p, out_r, rtol)
    probe = jax.random.normal(key(99), out_r.shape)
    grads = [jax.jit(jax.grad(lambda p, v, f=f: jnp.sum(f(p, v) * probe),
                              argnums=(0, 1)))(params, x)
             for f in (product, reference)]
    close(grads[0], grads[1], rtol)


# ------------------------------------------------------- the small layers
def test_rms_norm_against_reference():
    m = nn.RMSNorm(32, eps=1e-5)
    p, s = m.init(key(0))
    p = {"weight": 1.0 + 0.1 * jax.random.normal(key(1), (32,))}
    x = 3.0 * jax.random.normal(key(2), (2, 5, 32))
    both(lambda p, v: m.apply(p, s, v)[0],
         lambda p, v: ref.rms(v, p["weight"], 1e-5), p, x)


def test_gated_mlp_against_reference():
    m = nn.GatedMLP(32, 24)
    p, s = m.init(key(0))
    x = jax.random.normal(key(1), (2, 5, 32))
    both(lambda p, v: m.apply(p, s, v)[0],
         lambda p, v: ref.gated_mlp(v, p["w_in"], p["w_out"]), p, x)


# ------------------------------------------------------------- attention
@pytest.mark.parametrize("q_block", [None, 8, 5])
def test_grouped_query_attention_against_reference(q_block):
    m = nn.GroupedQueryAttention(32, 16, 8, scale=0.25, q_block=q_block)
    p, s = m.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    both(lambda p, v: m.apply(p, s, v)[0],
         lambda p, v: ref.attention(CFG, p, v), p, x)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_equal_repeated_heads(causal):
    """One function for both: grouped key/value heads read as the same
    heads repeated, blocked or not, masked or not."""
    q = jax.random.normal(key(0), (2, 8, 12, 4))
    k = jax.random.normal(key(1), (2, 2, 12, 4))
    v = jax.random.normal(key(2), (2, 2, 12, 4))
    mask = jax.random.bernoulli(key(3), 0.8, (2, 1, 12, 12)) \
        | jnp.eye(12, dtype=bool)
    want = dot_product_attention(q, jnp.repeat(k, 4, 1),
                                 jnp.repeat(v, 4, 1), causal=causal,
                                 mask=mask)
    for q_block in (None, 4, 5):
        close(dot_product_attention(q, k, v, causal=causal, mask=mask,
                                    q_block=q_block), want)


def test_blocked_decode_tail_offsets_its_queries():
    """Tq != Tk (a query tail) keeps its causal offset in every block."""
    q = jax.random.normal(key(0), (1, 4, 6, 4))
    k = jax.random.normal(key(1), (1, 2, 10, 4))
    v = jax.random.normal(key(2), (1, 2, 10, 4))
    close(dot_product_attention(q, k, v, causal=True, q_block=4),
          dot_product_attention(q, k, v, causal=True))


def test_heads_that_do_not_group_are_refused():
    q = jnp.zeros((1, 6, 4, 4))
    kv = jnp.zeros((1, 4, 4, 4))
    with pytest.raises(ValueError, match="not a multiple"):
        dot_product_attention(q, kv, kv)


# ----------------------------------------------------------------- mamba
def _scan_inputs(T, H=4, P=4, G=2, S=8):
    ks = jax.random.split(key(T), 5)
    x = jax.random.normal(ks[0], (2, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, T, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(ks[3], (2, T, G, S))
    C = jax.random.normal(ks[4], (2, T, G, S))
    return x, dt, A, B, C


@pytest.mark.parametrize("T", [8, 16, 24, 19],
                         ids=["1chunk", "2chunks", "3chunks", "ragged"])
def test_chunked_scan_against_the_recurrence(T):
    """Chunk 8: one, two and three chunks (state carried over none, one
    and two chunk boundaries), and a length that is no multiple."""
    x, dt, A, B, C = _scan_inputs(T)
    zero_d = jnp.zeros((4,))

    def product(args):
        return nn.ssd_chunked_scan(*args, chunk=8)

    def reference(args):
        return ref.recurrence(*args, zero_d)

    args = (x, dt, A, B, C)
    close(jax.jit(product)(args), jax.jit(reference)(args))
    probe = jax.random.normal(key(7), x.shape)
    close(*(jax.jit(jax.grad(lambda a, f=f: jnp.sum(f(a) * probe)))(args)
            for f in (product, reference)))


def test_conv_is_causal_and_depthwise():
    x = jax.random.normal(key(0), (1, 9, 3))
    w = jax.random.normal(key(1), (4, 3))
    b = jax.random.normal(key(2), (3,))
    y = nn.causal_depthwise_conv1d(x, w, b)
    want = np.zeros((9, 3))
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:
                want[t] += np.asarray(w[k]) * np.asarray(x[0, t - 3 + k])
    close(y[0], jnp.asarray(want, jnp.float32) + b)


def test_mamba_mixer_against_reference():
    m = nn.Mamba2Mixer(32, 16, 4, 8, chunk_size=8)
    p, s = m.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    both(lambda p, v: m.apply(p, s, v)[0],
         lambda p, v: ref.mamba_mixer(CFG, p, v), p, x)


def test_mamba_init_is_mamba2s():
    p, _ = nn.Mamba2Mixer(32, 16, 4, 8).init(key(0))
    a = np.exp(np.asarray(p["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.all(np.asarray(p["D"]) == 1.0)


# --------------------------------------------------------------- experts
def _moe(held=None, **kw):
    m = nn.ExpertParallelMoE(32, 16, 16, 4, held=held, **kw)
    p, s = m.init(key(0))
    return m, p, s


@pytest.mark.parametrize("held", [None, (4, 6)], ids=["all", "held4to6"])
def test_experts_against_the_masked_loop(held):
    m, p, s = _moe(held)
    x = jax.random.normal(key(1), (2, 24, 32))
    first = 0 if held is None else held[0]
    both(lambda p, v: m.apply(p, s, v)[0],
         lambda p, v: ref.routed_experts(CFG, p, v, first), p, x)
    _, new = m.apply(p, s, x)
    _, experts = m.route(p["router"], x.reshape(48, 32))
    lo, hi = m.held
    n_held = int(jnp.sum((experts >= lo) & (experts < hi)))
    assert count(new["rows_overflow"]) == 0
    assert count(new["rows_held"]) == n_held


def test_counters_are_running_totals():
    m, p, s = _moe((4, 6))
    x = jax.random.normal(key(1), (2, 24, 32))
    _, once = m.apply(p, s, x)
    _, twice = m.apply(p, once, x)
    assert count(twice["rows_held"]) == 2 * count(once["rows_held"]) > 0
    assert once["rows_held"].dtype == jnp.int32


def test_a_total_is_wider_than_one_word():
    """2e5 steps of 10,240 rows pass 2**31: the total carries."""
    near = jnp.array([1, COUNT_WORD - 5], jnp.int32)
    total = jax.jit(count_add)(near, jnp.int32(10240))
    assert total.dtype == jnp.int32
    assert count(total) == COUNT_WORD + COUNT_WORD - 5 + 10240 > 2 ** 31
    assert count(count_add(total, jnp.int32(0))) == count(total)


def test_a_bound_set_too_small_is_counted_not_silent():
    """R follows ``row_factor``; one that leaves fewer rows than the
    load drops assignments, and every one dropped is COUNTED."""
    m, p, s = _moe((4, 8))
    x = jax.random.normal(key(1), (2, 1024, 32))
    out_full, full = jax.jit(lambda v: m.apply(p, s, v))(x)
    need = count(full["rows_held"])
    assert count(full["rows_overflow"]) == 0 and need > 1024
    small, _, _ = _moe((4, 8), row_factor=0.5)
    rows = small.n_rows(2048)
    assert rows == 1024 < need
    out, new = jax.jit(lambda v: small.apply(p, s, v))(x)
    assert count(new["rows_held"]) == rows
    assert count(new["rows_overflow"]) == need - rows
    # what found no row reads 0: the result is NOT the full layer's
    assert float(jnp.max(jnp.abs(out - out_full))) > 1e-3
    assert bool(jnp.all(jnp.isfinite(out)))
    # ... and said in words, for the optimizer to log when a run ends
    assert m.state_warnings(full) == []
    said, = small.state_warnings(new)
    assert f"{need - rows} of {need} assignments" in said
    assert "row_factor 0.5" in said


def test_rows_follow_the_shapes_alone():
    assert nn.expert_rows(8192, 10, 9, 72, 1.5) == 15360
    m, _, _ = _moe((4, 6))
    assert m.n_rows(48) == 256          # never under one tile


# ------------------------------------------------------- the whole model
@pytest.mark.parametrize("share", [(0, 1), (3, SHARES)],
                         ids=["whole", "share3of8"])
def test_model_against_reference(share):
    m = granite_moe_hybrid(CFG, share, q_block=8)
    p, s = m.init(key(0))
    rows = CFG["vocab_size"] // share[1]
    ids = jax.random.randint(key(1), (2, 24), 0, rows)
    targets = jax.random.randint(key(2), (2, 24), 0, rows)
    logits, new = jax.jit(
        lambda p, v: m.apply(p, s, v, training=True))(p, ids)
    assert logits.shape == (2, 24, rows) and logits.dtype == jnp.float32
    close(logits, jax.jit(lambda p: ref.forward(CFG, share, p, ids))(p))
    assert all(count(l["experts"]["rows_overflow"]) == 0
               for l in new["layers"].values())

    def loss(p):
        return ref.cross_entropy(m.apply(p, s, ids, training=True)[0],
                                 targets)

    close(jax.jit(jax.grad(loss))(p),
          jax.jit(jax.grad(
              lambda p: ref.loss_fn(CFG, share, p, ids, targets)))(p))


# ------------------------------------- what a layer's checkpoint keeps
# what a layer tags, by the function that tags it: the last dimension of
# each array at CFG's sizes.  The k experts chosen; rows W_in and each
# row's assignment and gate (R = 256 rows for these 32 tokens: one
# ROW_TILE; its token and use are read from the assignment, not kept);
# x W_in of the shared expert.  Neither mixer tags anything
_TAGGED = {"LinearTopKRouter.route": [CFG["num_experts_per_tok"]],
           "ExpertParallelMoE.apply": [2 * CFG["intermediate_size"],
                                       256, 256],
           "GatedMLP.apply": [2 * CFG["shared_intermediate_size"]]}


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_a_layers_checkpoint_keeps_the_named_arrays_and_no_other(
        kind, capsys):
    """Beside its arguments a checkpointed layer of either kind saves
    the outputs of the expert block's two up-projections, what lays the
    experts' rows out, and nothing else."""
    layer = GraniteMoeHybridLayer(CFG, kind, q_block=8)
    p, s = layer.init(key(0))
    x = jax.random.normal(key(1), (2, 16, 32))
    jax.ad_checkpoint.print_saved_residuals(
        lambda p, v: checkpointed(layer)(p, s, v)[0].sum(), p, x)
    made = [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line]
    assert len(made) == sum(map(len, _TAGGED.values())), made
    for who, widths in _TAGGED.items():
        got = sorted(int(line.split("]")[0].split("[")[1].split(",")[-1])
                     for line in made if f"({who}" in line)
        assert got == sorted(widths), (who, made)


def test_gates_are_read_at_the_experts_chosen():
    """``route`` reads the logits at the chosen experts by a masked sum:
    the values ``top_k`` gives, to the last bit."""
    m, p, _ = _moe()
    x = jax.random.normal(key(1), (48, 32))
    gates, experts = m.route(p["router"], x)
    logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    values, chosen = jax.lax.top_k(logits, m.top_k)
    np.testing.assert_array_equal(experts, chosen)
    np.testing.assert_array_equal(gates, jax.nn.softmax(values, axis=-1))


@pytest.mark.parametrize("share", [(0, 1), (0, SHARES)],
                         ids=["whole", "share0of8"])
def test_gradients_with_the_policy_equal_a_bare_checkpoints(share,
                                                            monkeypatch):
    """The kept arrays are the ones the forward made: every parameter's
    gradient (the embedding's carries the first layer's input's) is the
    one a bare ``jax.checkpoint`` a layer gives, to f32 rounding."""
    m = granite_moe_hybrid(CFG, share, q_block=8)
    p, s = m.init(key(0))
    rows = CFG["vocab_size"] // share[1]
    ids = jax.random.randint(key(1), (2, 24), 0, rows)
    targets = jax.random.randint(key(2), (2, 24), 0, rows)

    def grads():
        return jax.jit(jax.grad(lambda p: ref.cross_entropy(
            m.apply(p, s, ids, training=True)[0], targets)))(p)

    kept = grads()
    # policy=None is jax.checkpoint's default: nothing kept by name
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    # the CPU compiles two programs that fuse, and so round, apart: 27
    # of 43 leaves came out equal to the last bit, the worst (a
    # two-element ``D``) 1.2e-6 of its larger element
    close(kept, grads(), rtol=5e-6)


@pytest.mark.parametrize("module", [
    nn.GatedMLP(32, 24), nn.ExpertParallelMoE(32, 16, 16, 4, held=(4, 6)),
], ids=["GatedMLP", "ExpertParallelMoE"])
def test_a_tag_outside_a_checkpoint_changes_nothing(module, monkeypatch):
    p, s = module.init(key(0))
    x = jax.random.normal(key(1), (2, 16, 32))

    def out_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p, v: module.apply(p, s, v)[0].sum(),
            argnums=(0, 1)))(p, x)

    tagged = out_and_grads()
    monkeypatch.setattr(nn.moe, "checkpoint_name", lambda a, name: a)
    for got, want in zip(jax.tree_util.tree_leaves(tagged),
                         jax.tree_util.tree_leaves(out_and_grads())):
        np.testing.assert_array_equal(got, want)


def test_counts_that_do_not_split_are_refused():
    with pytest.raises(ValueError, match="do not split"):
        granite_moe_hybrid(CFG, (0, 3))
    with pytest.raises(ValueError, match="index outside"):
        granite_moe_hybrid(CFG, (8, 8))


# ------------------------------------------------------------ the shares
def mamba_share(p, i, n, H, P, bc):
    """Chip ``i`` of ``n``'s slice of a whole Mamba layer's parameters:
    its heads' columns of z, x and dt, B and C whole."""
    h, d = H // n, H * P
    a, b = i * h * P, (i + 1) * h * P
    x0, bc0, dt0 = d, 2 * d, 2 * d + 2 * bc
    w = p["in_proj"]
    conv = lambda c: jnp.concatenate(
        [c[..., a:b], c[..., d:d + 2 * bc]], axis=-1)
    return {
        "in_proj": jnp.concatenate(
            [w[:, a:b], w[:, x0 + a:x0 + b], w[:, bc0:bc0 + 2 * bc],
             w[:, dt0 + i * h:dt0 + (i + 1) * h]], axis=1),
        "conv_w": conv(p["conv_w"]), "conv_b": conv(p["conv_b"]),
        "dt_bias": p["dt_bias"][i * h:(i + 1) * h],
        "A_log": p["A_log"][i * h:(i + 1) * h],
        "D": p["D"][i * h:(i + 1) * h],
        "norm_w": p["norm_w"][a:b], "out_proj": p["out_proj"][a:b]}


def test_eight_shares_of_a_mamba_layer_add_up():
    """The heads' partial sums add up to the uncut layer; the gated
    norm's mean of squares is summed over ``axis_name`` (here under
    ``vmap``), so every share normalizes over all of d_inner."""
    whole = nn.Mamba2Mixer(32, 16, 4, 8, chunk_size=8)
    p, _ = whole.init(key(0))
    p["norm_w"] = 1.0 + 0.1 * jax.random.normal(key(5), (64,))
    x = jax.random.normal(key(1), (2, 24, 32))
    part = nn.Mamba2Mixer(32, 16, 4, 8, chunk_size=8, held=(0, 2),
                          axis_name="tp")
    shares = [mamba_share(p, i, SHARES, 16, 4, 8) for i in range(SHARES)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *shares)
    assert jax.tree_util.tree_map(jnp.shape, shares[0]) == \
        jax.tree_util.tree_map(jnp.shape, part.init(key(0))[0])
    parts = jax.jit(jax.vmap(lambda q: part.apply(q, {}, x)[0],
                             axis_name="tp"))(stacked)
    close(parts.sum(0), jax.jit(lambda p: ref.mamba_mixer(CFG, p, x))(p))
    # without the exchange a share normalizes over its own heads only:
    # the layer then is NOT a slice of the whole one, and says so
    alone = nn.Mamba2Mixer(32, 16, 4, 8, chunk_size=8, held=(0, 2))
    assert float(jnp.max(jnp.abs(
        jax.jit(lambda q: alone.apply(q, {}, x)[0])(shares[0])
        - parts[0]))) > 1e-3


def test_eight_shares_of_an_attention_layer_add_up():
    whole = nn.GroupedQueryAttention(32, 16, 8, scale=0.25)
    p, _ = whole.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    dh, total = 2, 0.0
    for i in range(SHARES):
        part = nn.GroupedQueryAttention(32, 16, 8, held=(i, i + 1),
                                        scale=0.25, q_block=8)
        q = slice(i * 2 * dh, (i + 1) * 2 * dh)
        kv = slice(i * dh, (i + 1) * dh)
        total = total + part.apply(
            {"wq": p["wq"][:, q], "wk": p["wk"][:, kv],
             "wv": p["wv"][:, kv], "wo": p["wo"][q]}, {}, x)[0]
    close(total, ref.attention(CFG, p, x))


def test_eight_shares_of_an_expert_block_add_up():
    """Every chip routes over all 16 experts and computes its own two;
    the shared expert, which every chip computes alike, counts once."""
    whole, p, s = _moe()
    shared = nn.GatedMLP(32, 24)
    ps, _ = shared.init(key(3))
    x = jax.random.normal(key(1), (2, 24, 32))
    total, rows = shared.apply(ps, {}, x)[0], 0
    for i in range(SHARES):
        part, _, s_part = _moe((2 * i, 2 * i + 2))
        out, new = part.apply(
            {"router": p["router"], "w_in": p["w_in"][2 * i:2 * i + 2],
             "w_out": p["w_out"][2 * i:2 * i + 2]}, s_part, x)
        total, rows = total + out, rows + count(new["rows_held"])
        assert count(new["rows_overflow"]) == 0
    assert rows == 48 * 4               # every assignment, once
    close(total, ref.routed_experts(CFG, p, x, 0)
          + ref.gated_mlp(x, ps["w_in"], ps["w_out"]))


# --------------------------------------------------- through the optimizer
def test_trains_through_local_optimizer():
    """``LocalOptimizer.optimize()`` trains it as it trains PTB: bf16
    compute over f32 masters, the expert layers' counters carried out
    as model state, the loss falling on a task that can be learned."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, (32, 1))
    tokens = ((start + np.arange(17)) % 64).astype(np.int32)  # next = +1
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(4)
    model = granite_moe_hybrid(CFG, q_block=8)
    opt = optim.LocalOptimizer(
        model, ds, nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                               size_average=True))
    losses = []

    class Summary:
        def add_train_step(self, step, loss, lr, throughput):
            losses.append(float(loss))

        def add_scalar(self, *a, **k):
            pass

        def trigger_for(self, name):
            return None

    opt.set_optim_method(optim.SGD(learning_rate=2.0))
    opt.set_compute_dtype(jnp.bfloat16).set_seed(3)
    opt.set_train_summary(Summary())
    opt.set_end_when(optim.max_iteration(40))
    opt.optimize()
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1
    assert [c["rows_held"] for c in model.expert_counts(model._state)] \
        == [40 * 4 * 16 * 4] * 3           # steps x records x tokens x k
    assert model.state_warnings(model._state) == []
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(model._params))


def test_the_optimizer_says_when_assignments_were_dropped(caplog):
    """A share whose rows do not hold its load trains on, with some
    tokens missing an expert's part: the counters record it and the
    optimizer logs what they say when the run ends."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    tokens = np.random.default_rng(0).integers(
        0, 8, (4, 257)).astype(np.int32)
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(2)
    # 2 x 256 tokens x top-4, 8 of 16 experts held: a load of about
    # 1,024 assignments a layer; 0.1 of it leaves the one tile of 256
    model = granite_moe_hybrid(CFG, (0, 2), q_block=64, row_factor=0.1)
    opt = optim.LocalOptimizer(
        model, ds, nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                               size_average=True))
    opt.set_optim_method(optim.SGD(learning_rate=0.01)).set_seed(3)
    opt.set_end_when(optim.max_iteration(2))
    with caplog.at_level("WARNING", logger="bigdl_tpu.optim"):
        opt.optimize()
    counts = model.expert_counts(model._state)
    assert all(c["rows_overflow"] > 0 and c["rows_held"] == 2 * 256
               for c in counts)
    said = [r.getMessage() for r in caplog.records
            if "found no row" in r.getMessage()]
    assert len(said) == len(counts) and said[0].startswith("layer 0: ")
