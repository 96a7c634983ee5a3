"""``zaya`` and its new layers against the plain reference
(``benchmarks/references/zaya.py``: the convolutions as shifted sums
over the once-padded sequence, full attention scores, the experts as a
masked loop, the router written out), outputs AND gradients, at small
sizes on the CPU in f32 at the highest matmul precision, on seeded
weights.

Tolerances.  Product and reference compute the same f32 quantities in
another order (a grouped product against a masked loop, blocked against
full softmax, a projection shifted against a shifted input projected,
``rsqrt`` against a division by ``sqrt``), so they differ by f32
rounding that grows with the length of the sums: ``RTOL`` 2e-5 of the
largest element compared, as ``tests/test_granite_moe_hybrid.py``.  A
tap read from the wrong position, a head given the other half of the
values, a missed temperature or a gate that is not the chosen expert's
probability moves the result by 1e-2 or more.  What must hold exactly
(causality, the untouched channels, granite's routing) is compared bit
for bit.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.models import zaya
from bigdl_tpu.models.share import checkpointed
from bigdl_tpu.models.zaya import ZayaLayer
from bigdl_tpu.nn.moe import count_value as count

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zaya_reference", os.path.join(ROOT, "benchmarks", "references",
                                   "zaya.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

RTOL = 2e-5
SHARES = 2
THETA = 5000000.0

# a whole model small enough for the CPU: 8 query heads on 2 key/value
# heads (groups of 4, as published), 8 experts of which a token takes 1
CFG = dict(
    hidden_size=32, num_attention_heads=8, num_key_value_heads=2,
    head_dim=8, cca_time0=2, cca_time1=2, num_experts=8,
    num_experts_per_tok=1, moe_intermediate_size=16, router_hidden_size=12,
    rms_norm_eps=1e-5, num_hidden_layers=3, vocab_size=64,
    rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                "rope_theta": THETA}})


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


def _attention(held=None, q_block=8):
    m = nn.CompressedConvAttention(32, 8, 2, 8, conv=(2, 2),
                                   rotary=(THETA, 4), held=held,
                                   q_block=q_block)
    p, _ = m.init(key(0))
    # a temperature that is not its start, so that a missed one shows
    p["temp"] = 0.3 * jax.random.normal(key(5), p["temp"].shape)
    return m, p


# ------------------------------------------------------------------ rope
def test_rope_scores_depend_on_the_distance_alone():
    """``<rope(q)_i, rope(k)_j>`` for one q and one k at every pair of
    positions is a function of ``i - j``."""
    q = jax.random.normal(key(0), (1, 8))
    k = jax.random.normal(key(1), (1, 8))
    pos = jnp.arange(12)
    qs = nn.rope(jnp.repeat(q, 12, 0), pos, 100.0)
    ks = nn.rope(jnp.repeat(k, 12, 0), pos, 100.0)
    scores = np.asarray(qs @ ks.T)
    for d in range(-11, 12):
        diag = np.diagonal(scores, -d)
        assert np.ptp(diag) <= 1e-5 * np.abs(scores).max()
    assert np.ptp(scores) > 0.1            # ... and it does depend on it


def test_rope_leaves_the_channels_past_rotary_dim_untouched():
    x = jax.random.normal(key(0), (2, 3, 10, 8))
    y = nn.rope(x, jnp.arange(10), THETA, 4)
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
    assert float(jnp.max(jnp.abs(y[..., 1:, :4] - x[..., 1:, :4]))) > 1e-3
    np.testing.assert_array_equal(y[..., 0, :], x[..., 0, :])   # angle 0


def test_rope_keeps_the_length_and_is_hfs_pairing():
    x = jax.random.normal(key(0), (2, 10, 3, 8))       # (N, T, H, Dh)
    y = nn.rope(x.transpose(0, 2, 1, 3), jnp.arange(10), THETA, 4)
    close(jnp.linalg.norm(y, axis=-1),
          jnp.linalg.norm(x, axis=-1).transpose(0, 2, 1))
    close(y.transpose(0, 2, 1, 3), ref.rotate(x, THETA, 4))


# ------------------------------------------------- compressed attention
@pytest.mark.parametrize("q_block", [None, 8, 5])
def test_compressed_conv_attention_against_reference(q_block):
    """Whole, blocked, and blocked with a ragged last block: all against
    the reference's full scores (one block)."""
    m, p = _attention(q_block=q_block)
    x = jax.random.normal(key(1), (2, 24, 32))
    both(lambda p, v: m.apply(p, {}, v)[0],
         lambda p, v: ref.attention(CFG, p, v), p, x)


def test_the_mix_is_causal():
    """Queries and keys at position t do not move, to the last bit, when
    the input changes after t."""
    m, p = _attention()
    q0 = jax.random.normal(key(1), (1, 12, 64))
    k0 = jax.random.normal(key(2), (1, 12, 16))
    q, k = m.mix(p, q0, k0)
    q2, k2 = m.mix(p, q0.at[:, 7:].add(1.0), k0.at[:, 7:].add(-1.0))
    for a, b in ((q, q2), (k, k2)):
        np.testing.assert_array_equal(a[:, :, :7], b[:, :, :7])
        assert float(jnp.max(jnp.abs(a[:, :, 7] - b[:, :, 7]))) > 1e-3


def test_the_mix_reads_exactly_two_positions_back():
    """Position t reads t-2..t (k0 - 1 + k1 - 1 = 2): a change at s
    moves s, s+1, s+2 and nothing else."""
    m, p = _attention()
    q0 = jax.random.normal(key(1), (1, 12, 64))
    k0 = jax.random.normal(key(2), (1, 12, 16))
    q, k = m.mix(p, q0, k0)
    q2, k2 = m.mix(p, q0.at[:, 4].add(1.0), k0.at[:, 4].add(1.0))
    for a, b in ((q, q2), (k, k2)):
        moved = np.asarray(jnp.max(jnp.abs(a - b), axis=(0, 1, 3)))
        assert list(np.nonzero(moved)[0]) == [4, 5, 6]


def test_the_second_value_head_reads_the_previous_token():
    """Of the whole layer's two key/value heads the first reads the
    token and the second the one before it.  A query head of group 1 at
    position 0 therefore attends to zeros, and at position 1 to nothing
    but the first token's value."""
    # hidden = the latent's 64 channels and W_o the identity: the layer's
    # output is then the heads' own
    m = nn.CompressedConvAttention(64, 8, 2, 8, rotary=(THETA, 4),
                                   q_block=None)
    p = dict(m.init(key(0))[0], wo=jnp.eye(64))
    x = jax.random.normal(key(1), (1, 10, 64))

    def heads(v):
        return m.apply(p, {}, v)[0].reshape(1, 10, 8, 8)

    o = heads(x)
    assert float(jnp.max(jnp.abs(o[0, 0, 4:]))) == 0.0    # group 1 at t=0
    assert float(jnp.max(jnp.abs(o[0, 0, :4]))) > 1e-3    # group 0 is not
    # at position 1 group 1 has read zeros (key 0) and value(token 0)
    # (key 1): each of its heads gives that value times its weight on it
    v_all = x @ p["wv"]
    for head in o[0, 1, 4:]:
        weight = jnp.vdot(head, v_all[0, 0, 8:]) / jnp.vdot(
            v_all[0, 0, 8:], v_all[0, 0, 8:])
        assert 0.0 < float(weight) < 1.0
        close(head, weight * v_all[0, 0, 8:])
    close(o[0, 0, :4], jnp.broadcast_to(v_all[0, 0, :8], (4, 8)))


def test_heads_that_do_not_group_are_refused():
    with pytest.raises(ValueError, match="not a multiple"):
        nn.CompressedConvAttention(32, 6, 4, 8)
    with pytest.raises(ValueError, match="outside"):
        nn.CompressedConvAttention(32, 8, 2, 8, held=(1, 3))


# ---------------------------------------------------------------- router
def _router(first=False):
    m = nn.MLPRouter(32, 8, 1, 12, first=first)
    p, s = m.init(key(0))
    # biases, gain and norm off their starts, so that a missed one shows
    for i, name in enumerate(sorted(set(p) - {"wd", "w1", "w2", "w3"})):
        p[name] = p[name] + 0.2 * jax.random.normal(key(10 + i),
                                                    p[name].shape)
    return m, p, s


@pytest.mark.parametrize("first", [True, False], ids=["first", "later"])
def test_mlp_router_against_reference(first):
    m, p, s = _router(first)
    x = jax.random.normal(key(1), (48, 32))
    r_prev = jax.random.normal(key(2), (48, 12))
    gates, experts, r = jax.jit(lambda p: m.route(p, s, x, r_prev))(p)
    prob, chosen, r_ref = jax.jit(lambda p: ref.router(
        CFG, p, x, None if first else r_prev))(p)
    np.testing.assert_array_equal(experts, chosen)
    close(r, r_ref)
    # the gate is the chosen expert's probability over ALL experts
    close(gates, jnp.take_along_axis(prob, chosen, axis=-1))
    assert float(gates.max()) < 1.0 and gates.shape == (48, 1)
    assert "g" not in p if first else "g" in p


def test_the_routers_gradient_reaches_every_one_of_its_weights():
    """With one expert a token the gate is NOT constant: the loss moves
    every matrix of the router, the down-projection and the gain on the
    previous layer's state."""
    layer = nn.ExpertParallelMoE(32, 16, 8, 1, router=_router()[0])
    p, s = layer.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    r_prev = jax.random.normal(key(2), (2, 24, 12))
    g = jax.jit(jax.grad(
        lambda p: jnp.sum(layer.apply(p, s, (x, r_prev))[0][0] ** 2)))(p)
    for name in ("wd", "bd", "g", "norm", "w1", "b1", "w2", "b2", "w3"):
        assert float(jnp.max(jnp.abs(g["router"][name]))) > 1e-6, name
    # ... which a softmax over the ONE chosen logit cannot give
    linear = nn.ExpertParallelMoE(32, 16, 8, 1)
    p, s = linear.init(key(0))
    g = jax.jit(jax.grad(
        lambda p: jnp.sum(linear.apply(p, s, x)[0] ** 2)))(p)
    assert float(jnp.max(jnp.abs(g["router"]))) == 0.0


def test_gradients_with_remat_and_without_agree():
    """A layer under its checkpoint (the choice, the rows' layout and
    ``rows W_in`` kept by name, the rest made a second time) gives the
    gradients of the same layer differentiated as it stands."""
    layer = ZayaLayer(CFG, q_block=8)
    p, s = layer.init(key(0))
    h = jax.random.normal(key(1), (2, 16, 32))
    r = jax.random.normal(key(2), (2, 16, 12))

    def loss(apply):
        def f(p, h, r):
            (h2, r2), _ = apply(p, s, (h, r))
            return jnp.sum(h2 ** 2) + jnp.sum(r2 ** 2)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(p, h, r)

    close(loss(checkpointed(layer)), loss(layer.apply), rtol=5e-6)


def _old_linear_route(router, x, n_experts, top_k):
    """``ExpertParallelMoE.route`` as PR 33 left it, word for word."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, experts = jax.lax.top_k(logits, top_k)
    chosen = experts[..., None] == jnp.arange(n_experts)
    values = jnp.sum(jnp.where(chosen, logits[:, None, :], 0), axis=-1)
    return jax.nn.softmax(values, axis=-1), experts


def test_linear_router_is_granites_bit_for_bit():
    """The arithmetic moved, nothing else: the same weights from the
    same key, the same gates and experts to the last bit, and a layer
    given the router by hand is the layer built without one."""
    layer = nn.ExpertParallelMoE(32, 16, 16, 4, held=(4, 6))
    p, s = layer.init(key(0))
    from bigdl_tpu.nn.initialization import Xavier
    np.testing.assert_array_equal(
        p["router"], Xavier().init(jax.random.split(key(0), 3)[0],
                                   (32, 16), 32, 16))
    x = jax.random.normal(key(1), (2, 24, 32))
    flat = x.reshape(48, 32)
    for got, want in zip(
            jax.jit(lambda r: layer.router.route(r, {}, flat))(p["router"]),
            jax.jit(lambda r: _old_linear_route(r, flat, 16, 4))(
                p["router"])):
        np.testing.assert_array_equal(got, want)
    given = nn.ExpertParallelMoE(
        32, 16, 16, 4, held=(4, 6), router=nn.LinearTopKRouter(32, 16, 4))
    np.testing.assert_array_equal(
        jax.jit(lambda p: layer.apply(p, s, x)[0])(p),
        jax.jit(lambda p: given.apply(p, s, x)[0])(p))


# --------------------------------------------------------------- experts
@pytest.mark.parametrize("held", [None, (4, 8)], ids=["all", "held4to8"])
def test_top1_experts_against_the_masked_loop(held):
    layer = nn.ExpertParallelMoE(32, 16, 8, 1, held=held,
                                 router=_router()[0])
    p, s = layer.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    r_prev = jax.random.normal(key(2), (2, 24, 12))
    first = 0 if held is None else held[0]
    both(lambda p, v: layer.apply(p, s, (v, r_prev))[0][0],
         lambda p, v: ref.routed_experts(CFG, p, v, r_prev, first)[0], p, x)
    both(lambda p, v: layer.apply(p, s, (v, r_prev))[0][1],
         lambda p, v: ref.routed_experts(CFG, p, v, r_prev, first)[1], p, x)


@pytest.mark.parametrize("row_factor", [2.0, 0.25], ids=["fits", "drops"])
def test_rows_by_expert_sum_to_rows_held(row_factor):
    """The held experts' totals split ``rows_held``: with every
    assignment computed, and with some dropped (only the computed ones
    are counted, and the padding counted to the last group is not)."""
    layer = nn.ExpertParallelMoE(32, 16, 8, 1, held=(2, 6),
                                 row_factor=row_factor,
                                 router=_router(first=True)[0])
    p, s = layer.init(key(0))
    x = jax.random.normal(key(1), (2, 1024, 32))
    r0 = jnp.zeros((2, 1024, 12))
    _, once = jax.jit(lambda v: layer.apply(p, s, (v, r0)))(x)
    _, twice = jax.jit(lambda v: layer.apply(p, once, (v, r0)))(x)
    by_expert = [count(t) for t in twice["rows_by_expert"]]
    assert twice["rows_by_expert"].shape == (4, 2)
    assert sum(by_expert) == count(twice["rows_held"]) > 0
    assert by_expert == [2 * count(t) for t in once["rows_by_expert"]]
    _, experts, _ = layer.router.route(p["router"], s["router"],
                                       x.reshape(2048, 32), r0)
    sent = [int(jnp.sum(experts == e)) for e in range(2, 6)]
    if row_factor == 2.0:
        assert count(once["rows_overflow"]) == 0
        assert [count(t) for t in once["rows_by_expert"]] == sent
    else:
        assert count(once["rows_overflow"]) > 0
        assert count(once["rows_held"]) == layer.n_rows(2048) < sum(sent)


# ------------------------------------------------------------ the shares
def attention_share(p, i):
    """Chip ``i`` of 2's slice of the whole attention layer's
    parameters: its key/value group's columns, channels, groups,
    temperature and rows."""
    G, dh, hq = 4, 8, 8
    q = np.arange(i * G * dh, (i + 1) * G * dh)
    kv = np.arange(i * dh, (i + 1) * dh)
    channels = np.concatenate([q, hq * dh + kv])
    groups = np.concatenate([np.arange(i * G, (i + 1) * G), [hq + i]])
    return {"wq": p["wq"][:, q], "wk": p["wk"][:, kv], "wv": p["wv"][:, kv],
            "conv0_w": p["conv0_w"][:, channels],
            "conv0_b": p["conv0_b"][channels],
            "conv1_w": p["conv1_w"][:, groups],
            "conv1_b": p["conv1_b"][channels],
            "temp": p["temp"][i:i + 1], "wo": p["wo"][q]}


def test_two_shares_of_an_attention_layer_add_up():
    """Each chip's group (share 0's values from the token, share 1's
    from the one before) gives its partial sum; together the uncut
    reference layer."""
    _, p = _attention()
    x = jax.random.normal(key(1), (2, 24, 32))
    total = 0.0
    for i in range(SHARES):
        part, _ = _attention(held=(i, i + 1))
        mine = attention_share(p, i)
        assert jax.tree_util.tree_map(jnp.shape, mine) == \
            jax.tree_util.tree_map(jnp.shape, part.init(key(0))[0])
        total = total + part.apply(mine, {}, x)[0]
        # a share alone is the reference given the same share
        close(part.apply(mine, {}, x)[0], ref.attention(CFG, mine, x, i))
    close(total, ref.attention(CFG, p, x))


def test_two_shares_of_an_expert_block_add_up():
    """Every chip routes over all 8 experts with the WHOLE router and
    computes its own four; the router's state, which every chip computes
    alike, counts once."""
    router = _router()[0]
    whole = nn.ExpertParallelMoE(32, 16, 8, 1, router=router)
    p, _ = whole.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    r_prev = jax.random.normal(key(2), (2, 24, 12))
    want, r_want = ref.routed_experts(CFG, p, x, r_prev, 0)
    total, rows = 0.0, 0
    for i in range(SHARES):
        part = nn.ExpertParallelMoE(32, 16, 8, 1, held=(4 * i, 4 * i + 4),
                                    router=router)
        (out, r), new = part.apply(
            {"router": p["router"], "w_in": p["w_in"][4 * i:4 * i + 4],
             "w_out": p["w_out"][4 * i:4 * i + 4]},
            part.init(key(0))[1], (x, r_prev))
        total, rows = total + out, rows + count(new["rows_held"])
        close(r, r_want)
    assert rows == 48                   # every token's one expert, once
    close(total, want)


# ------------------------------------------------------- the whole model
@pytest.mark.parametrize("share,vocab_share", [
    ((0, 1), None), ((1, SHARES), None), ((1, SHARES), (3, 8))],
    ids=["whole", "share1of2", "share1of2-rows3of8"])
def test_model_against_reference(share, vocab_share):
    m = zaya(CFG, share, vocab_share=vocab_share, q_block=8)
    p, s = m.init(key(0))
    rows = CFG["vocab_size"] // (vocab_share or share)[1]
    ids = jax.random.randint(key(1), (2, 24), 0, rows)
    targets = jax.random.randint(key(2), (2, 24), 0, rows)
    logits, new = jax.jit(
        lambda p, v: m.apply(p, s, v, training=True))(p, ids)
    assert logits.shape == (2, 24, rows) and logits.dtype == jnp.float32
    close(logits, jax.jit(lambda p: ref.forward(CFG, share, p, ids))(p))
    counts = m.expert_counts(new)
    assert all(c["rows_overflow"] == 0 for c in counts)
    assert all(sum(c["rows_by_expert"]) == c["rows_held"] for c in counts)
    if share == (0, 1):
        assert [c["rows_held"] for c in counts] == [48] * 3

    def loss(p):
        return ref.cross_entropy(m.apply(p, s, ids, training=True)[0],
                                 targets)

    grads = jax.jit(jax.grad(loss))(p)
    # atol: a temperature's gradient is ONE number, 6e-6 here, the sum
    # of 48 x 4 x 24 terms of either sign a thousand times its size: it
    # carries their rounding (read 2.4e-9) and has no larger neighbour
    # in its leaf to be measured against
    close(grads, jax.jit(jax.grad(
        lambda p: ref.loss_fn(CFG, share, p, ids, targets)))(p), atol=1e-8)
    # every parameter learns, the router's through the gate
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert all(float(jnp.max(jnp.abs(g))) > 0 for _, g in flat), \
        [jax.tree_util.keystr(k) for k, g in flat
         if float(jnp.max(jnp.abs(g))) == 0]


def test_counts_that_do_not_split_are_refused():
    with pytest.raises(ValueError, match="do not split"):
        zaya(CFG, (0, 3))
    with pytest.raises(ValueError, match="index outside"):
        zaya(CFG, (2, 2))
    with pytest.raises(ValueError, match="vocab_share"):
        zaya(CFG, (0, 2), vocab_share=(8, 8))


# --------------------------------------------------- through the optimizer
def _optimizer(model, ds, lr, steps):
    opt = optim.LocalOptimizer(
        model, ds, nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                               size_average=True))
    opt.set_optim_method(optim.SGD(learning_rate=lr)).set_seed(3)
    return opt.set_end_when(optim.max_iteration(steps))


def test_one_sgd_step_through_the_optimizer_against_reference():
    """The comparison the benchmark's runner makes, at the tiny size in
    f32: ``LocalOptimizer.optimize()`` for one step against the
    reference's ``make_sgd_step`` on weights drawn from the same key."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    share, rows = (1, SHARES), 32
    tokens = np.random.default_rng(0).integers(
        0, rows, (2, 25)).astype(np.int32)
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(2)
    model = zaya(CFG, share, q_block=8)
    _optimizer(model, ds, 0.5, 1).optimize()
    p0, _ = model.init(jax.random.split(key(3))[1])
    loss, want = ref.make_sgd_step(CFG, share)(
        jax.tree_util.tree_map(jnp.copy, p0), tokens[:, :-1],
        tokens[:, 1:], np.float32(0.5))
    assert abs(float(loss) - np.log(rows)) < 0.1
    moved = jax.tree_util.tree_map(lambda a, b: a - b, model._params, p0)
    # the change is a difference of two close numbers, so it carries the
    # ulps of the weights themselves (2e-7 of a weight of 1, which the
    # scalings are): a gradient as under test_model_against_reference,
    # plus that
    close(moved, jax.tree_util.tree_map(lambda a, b: a - b, want, p0),
          atol=2.5e-7)
    assert all(float(jnp.max(jnp.abs(m))) > 0
               for m in jax.tree_util.tree_leaves(moved))


def test_trains_through_local_optimizer():
    """``LocalOptimizer.optimize()`` trains it as it trains granite: bf16
    compute over f32 masters, the router's state crossing each layer's
    checkpoint, the counters carried out as model state, the loss
    falling on a task that can be learned."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, (32, 1))
    tokens = ((start + np.arange(17)) % 64).astype(np.int32)  # next = +1
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(4)
    model = zaya(CFG, q_block=8, row_factor=2.0)
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
    assert [c["rows_held"] for c in counts] == [40 * 4 * 16] * 3
    assert all(sum(c["rows_by_expert"]) == c["rows_held"] for c in counts)
    assert model.state_warnings(model._state) == []
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(model._params))
