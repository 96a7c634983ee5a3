"""``qwen3_next`` and its new layers against the plain reference
(``benchmarks/references/qwen3_next.py``: the delta rule token by token,
the convolution as shifted sums, full attention scores, routing as a
softmax over all experts then the top k renormalised, the experts as a
masked dense loop), outputs AND gradients, at small sizes on the CPU in
f32 at the highest matmul precision, on seeded weights.

Tolerances.  Product and reference compute the same f32 quantities in
another order (a chunk's triangular solve against the recurrence step by
step, a grouped product against a masked loop, blocked against full
softmax, ``rsqrt`` against a division by ``sqrt``): ``RTOL`` 2e-5 of the
largest element compared, as ``tests/test_zaya.py``.  The chunked rule
alone reads 1e-7 to 3e-7 of it.  A step read from the wrong chunk, a
decay left out, the norm and the gate taken in the other order or a gate
renormalised otherwise moves the result by 1e-2 or more.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.models import qwen3_next
from bigdl_tpu.models.qwen3_next import Qwen3NextLayer, layer_types
from bigdl_tpu.nn import gated_delta
from bigdl_tpu.nn.gated_delta import chunked_gated_delta_rule, unit_length
from bigdl_tpu.nn.moe import count_value as count

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "qwen3_next_reference", os.path.join(ROOT, "benchmarks", "references",
                                         "qwen3_next.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

RTOL = 2e-5
SHARES = 8

# a whole model small enough for the CPU: one period (3 linear layers, 1
# full), 2 key heads on 4 value heads (pairs, as published), 4 query heads
# on 2 key/value heads, a quarter of each head rotary, 16 experts top-4
CFG = dict(
    hidden_size=32, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=10000000, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=8,
    shared_expert_intermediate_size=8, full_attention_interval=4,
    num_hidden_layers=4, vocab_size=64, rms_norm_eps=1e-6)


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
    """``params`` with every vector moved off its start (a norm's 0 or
    1, ``dt_bias``' 1), so that a weight read wrongly shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key(n), len(leaves))
    return tree.unflatten([
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for k, a in zip(keys, leaves)])


def test_layer_types_follow_the_interval():
    assert layer_types(dict(CFG, num_hidden_layers=8)) == \
        ["linear_attention"] * 3 + ["full_attention"] \
        + ["linear_attention"] * 3 + ["full_attention"]
    assert layer_types(CFG) == ref.layer_types(CFG)


# ------------------------------------------------------ the delta rule
# (N, T, key heads, value heads, K, V): the XLA form's small size, and
# the kernel's: two value heads on one key head, heads of 128 lanes, four
# chunks of 64 and a padded tail of 5
SIZES = {"small": (2, 37, 3, 3, 8, 6), "aligned": (1, 4 * 64 + 5, 1, 2, 128,
                                                   128)}


def _rule_inputs(decay, size="small"):
    """``equal_keys``: one key at every step, ``beta`` 1 and ``g`` 0, so
    ``A`` is all ones under the diagonal: the case in which a doubling
    product form of ``(I + A)^{-1}`` is off by 1e9 in f32 and a
    substitution is not."""
    N, T, Hk, H, K, V = SIZES[size]
    ks = jax.random.split(key(0), 5)
    g = {"none": jnp.zeros((N, T, H)), "equal_keys": jnp.zeros((N, T, H)),
         "drawn": -jax.nn.softplus(jax.random.normal(ks[4], (N, T, H))),
         "total": jnp.full((N, T, H), -30.0)}[decay]
    k = unit_length(jax.random.normal(ks[1], (N, T, Hk, K)))
    beta = jax.random.uniform(ks[3], (N, T, H))
    if decay == "equal_keys":
        k, beta = jnp.broadcast_to(k[:, :1], k.shape), jnp.ones_like(beta)
    return (unit_length(jax.random.normal(ks[0], (N, T, Hk, K))) / np.sqrt(K),
            k, jax.random.normal(ks[2], (N, T, H, V)), g, beta)


def _per_value_head(rule):
    """``rule`` on ``q`` and ``k`` repeated to the value heads, as the
    reference takes them."""
    def run(q, k, v, g, beta):
        r = v.shape[2] // k.shape[2]
        return rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v,
                    g, beta)
    return run


@pytest.fixture
def kernel_impl():
    """``Engine.set_kernel_impl`` for one test, the backend's before and
    after it."""
    from bigdl_tpu.engine import Engine
    prev = Engine._state.kernel_impl
    yield Engine.set_kernel_impl
    Engine._state.kernel_impl = prev


def _out_and_grads(rule, args, probe):
    return (jax.jit(rule)(*args),) + tuple(jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a) * probe), argnums=(0, 1, 2, 3, 4)))(*args))


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("decay", ["none", "drawn", "total", "equal_keys"])
def test_chunked_rule_against_the_recurrence(chunk, decay, kernel_impl):
    """T = 37 is no multiple of chunks of 4 or 16.  ``g = 0`` keeps every
    state whole (the triangular system is widest); ``g = -30`` forgets
    all but the last step (every decay across a step is 1e-13).  At
    chunks of 64 the size is the kernel's (``SIZES["aligned"]``): the
    Pallas kernel, interpreted here, against the recurrence and against
    the XLA form, in values and in all five inputs' gradients."""
    from bigdl_tpu.ops import pallas_delta_rule
    size = "aligned" if chunk == 64 else "small"
    args = _rule_inputs(decay, size)
    probe = jax.random.normal(key(9), args[2].shape)
    rule = lambda *a: chunked_gated_delta_rule(*a, chunk=chunk)  # noqa
    kernel_impl("pallas")
    assert pallas_delta_rule.delta_rule_path(
        args[1].shape, args[2].shape, args[2].dtype, chunk)[0] == \
        ("kernel" if size == "aligned" else "xla")
    got = _out_and_grads(rule, args, probe)
    assert got[0].shape == args[2].shape and got[0].dtype == jnp.float32
    want = _out_and_grads(_per_value_head(ref.delta_rule), args, probe)
    # atol: under total decay the true gradient of g is 6e-14, what the
    # chunk's O(1) terms cancel to; the chunked form reads their
    # rounding.  Under equal keys each step's state is k v_t^T whatever
    # g was, so the gradient of g is 0, read as the rounding of terms of
    # up to 11 (2.9e-6 by the recurrence, 5.7e-6 off it by the kernel)
    atol = 2e-5 if decay == "equal_keys" else 1e-9
    close(got[0], want[0])
    close(got[1:], want[1:], atol=atol)
    if size == "aligned":
        kernel_impl("xla")
        xla = _out_and_grads(
            lambda *a: chunked_gated_delta_rule(*a, chunk=chunk), args, probe)
        close(got[0], xla[0])
        close(got[1:], xla[1:], atol=atol)


def test_padded_steps_leave_the_state_as_it_is():
    """The same sequence in one chunk of its own length and in chunks
    of 8 with a tail of 3 padded steps: the real steps read alike."""
    q, k, v, g, beta = _rule_inputs("drawn")
    whole = chunked_gated_delta_rule(q, k, v, g, beta, chunk=37)
    padded = chunked_gated_delta_rule(q, k, v, g, beta, chunk=8)
    close(padded, whole)


# reason: (k shape, v shape, v dtype, chunk, kernel_impl); beside the
# kernel's own (1, 128, 1, 128) keys on (1, 128, 2, 128) values, f32, 64
DELTA_RULE_XLA = {
    "shapes": ((1, 128, 1, 128), (1, 64, 2, 128), jnp.float32, 64,
               "pallas"),
    "value heads not a multiple of key heads": (
        (1, 128, 2, 128), (1, 128, 3, 128), jnp.float32, 64, "pallas"),
    "value heads a key head": ((1, 128, 1, 128), (1, 128, 16, 128),
                               jnp.float32, 64, "pallas"),
    "narrow head": ((1, 128, 1, 64), (1, 128, 2, 128), jnp.float32, 64,
                    "pallas"),
    "chunk not a multiple of 16": ((1, 128, 1, 128), (1, 128, 2, 128),
                                   jnp.float32, 24, "pallas"),
    "dtype": ((1, 128, 1, 128), (1, 128, 2, 128), jnp.float16, 64,
              "pallas"),
    "kernel_impl xla": ((1, 128, 1, 128), (1, 128, 2, 128), jnp.float32,
                        64, "xla"),
}


@pytest.mark.parametrize("reason", sorted(DELTA_RULE_XLA))
def test_delta_rule_path_says_why_it_takes_xla(reason, kernel_impl):
    """Every reason ``delta_rule_path`` gives for the XLA form; the
    kernel's own shapes in bf16 take the kernel."""
    from bigdl_tpu.ops import pallas_delta_rule
    k_shape, v_shape, dtype, chunk, impl = DELTA_RULE_XLA[reason]
    kernel_impl(impl)
    assert pallas_delta_rule.delta_rule_path(
        k_shape, v_shape, dtype, chunk) == ("xla", reason)
    kernel_impl("pallas")
    assert pallas_delta_rule.delta_rule_path(
        (1, 128, 1, 128), (1, 128, 2, 128), jnp.bfloat16, 64)[0] == "kernel"


def _gdn():
    m = nn.GatedDeltaNet(32, 2, 4, 8, 8, conv_kernel=4)
    return m, moved(m.init(key(0))[0])


def test_gated_delta_net_against_reference():
    m, p = _gdn()
    x = jax.random.normal(key(1), (2, 20, 32))
    run = lambda p, x: m.apply(p, {}, x)[0]
    want = lambda p, x: ref.gated_delta_net(CFG, p, x)
    close(jax.jit(run)(p, x), jax.jit(want)(p, x))
    probe = jax.random.normal(key(9), (2, 20, 32))
    grads = [jax.jit(jax.grad(lambda p, x, f=f: jnp.sum(f(p, x) * probe),
                              argnums=(0, 1)))(p, x) for f in (run, want)]
    # atol: ``A_log``'s and ``dt_bias``' gradients are one number a head,
    # a sum over every step and channel of terms of either sign that
    # cancel to 1e-7 of the largest (read 5e-8 off): they carry the
    # rounding of those terms, as the chunked rule's ``g`` does
    close(grads[0], grads[1], atol=1e-7)


def test_the_norm_comes_before_the_gate(monkeypatch):
    """Mamba-2's ``gated_rms_norm`` gates first; this layer normalises
    first.  Swapped in, the comparison above fails by far."""
    m, p = _gdn()
    x = jax.random.normal(key(1), (2, 20, 32))
    want = ref.gated_delta_net(CFG, p, x)
    monkeypatch.setattr(gated_delta, "normed_then_gated",
                        lambda y, z, w, eps: nn.gated_rms_norm(y, z, w, eps))
    got = m.apply(p, {}, x)[0]
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))


def test_the_layer_is_causal():
    """Steps from 70 on changed, in the second chunk: every step before
    reads as it did, those of its own chunk included."""
    m, p = _gdn()
    x = jax.random.normal(key(1), (1, 80, 32))
    later = x.at[:, 70:].set(jax.random.normal(key(2), (1, 10, 32)))
    a, b = m.apply(p, {}, x)[0], m.apply(p, {}, later)[0]
    assert bool(jnp.all(a[:, :70] == b[:, :70]))
    assert not bool(jnp.all(a[:, 70:] == b[:, 70:]))


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("q_block", [8, None])
def test_gated_attention_against_reference(q_block):
    m = nn.GatedAttention(32, 4, 2, 16, rotary=(1e7, 4), q_block=q_block)
    p = moved(m.init(key(0))[0])
    x = jax.random.normal(key(1), (2, 20, 32))
    run = lambda p, x: m.apply(p, {}, x)[0]
    want = lambda p, x: ref.attention(CFG, p, x)
    close(jax.jit(run)(p, x), jax.jit(want)(p, x))
    probe = jax.random.normal(key(9), (2, 20, 32))
    grads = [jax.jit(jax.grad(lambda p, x, f=f: jnp.sum(f(p, x) * probe),
                              argnums=(0, 1)))(p, x) for f in (run, want)]
    close(grads[0], grads[1])


# ------------------------------------------------------ router, experts
def test_router_is_the_published_softmax_top_k_renormalised():
    """At the published 512 experts and top 10: ``LinearTopKRouter``'s
    softmax over the ten chosen logits is HF's softmax over all 512, the
    top ten taken and renormalised."""
    router = nn.LinearTopKRouter(64, 512, 10)
    w = 0.5 * jax.random.normal(key(0), (64, 512))
    x = jax.random.normal(key(1), (128, 64))
    gates, experts = router.route(w, {}, x)
    prob = jax.nn.softmax(x @ w, axis=-1)
    top, chosen = jax.lax.top_k(prob, 10)
    assert bool(jnp.all(experts == chosen))
    close(gates, top / jnp.sum(top, axis=-1, keepdims=True), rtol=1e-6)


def test_eight_expert_shares_add_up_to_the_uncut_block():
    """Every chip routes over all 16 experts with the WHOLE router and
    computes its own two; the gated shared expert, which every chip
    computes alike, counts once: together the reference's uncut expert
    block."""
    whole = Qwen3NextLayer(CFG, "linear_attention")
    p, _ = whole.init(key(0))
    x = jax.random.normal(key(1), (2, 24, 32))
    want = ref.expert_block(CFG, p, x, 0)
    shared = jax.nn.sigmoid(x @ p["shared_gate"]) \
        * whole.shared.apply(p["shared"], {}, x)[0]
    total, rows = shared, 0
    per = CFG["num_experts"] // SHARES
    for i in range(SHARES):
        part = Qwen3NextLayer(CFG, "linear_attention", (i, SHARES)).experts
        mine = {"router": p["experts"]["router"],
                "w_in": p["experts"]["w_in"][per * i:per * (i + 1)],
                "w_out": p["experts"]["w_out"][per * i:per * (i + 1)]}
        assert jax.tree_util.tree_map(jnp.shape, mine) == \
            jax.tree_util.tree_map(jnp.shape, part.init(key(0))[0])
        out, new = part.apply(mine, part.init(key(0))[1], x)
        total, rows = total + out, rows + count(new["rows_held"])
    assert rows == 2 * 24 * 4            # every assignment, once
    close(total, want)


# ------------------------------------------------------- the whole model
def _model_params(m):
    p, s = m.init(key(0))
    return moved(p), s


@pytest.mark.parametrize("share,vocab_share", [
    ((0, 1), (0, 1)), ((3, SHARES), (5, 8))],
    ids=["whole", "experts3of8-rows5of8"])
def test_model_against_reference(share, vocab_share):
    m = qwen3_next(CFG, share, vocab_share=vocab_share, q_block=8)
    p, s = _model_params(m)
    rows = CFG["vocab_size"] // vocab_share[1]
    ids = jax.random.randint(key(1), (2, 20), 0, rows)
    targets = jax.random.randint(key(2), (2, 20), 0, rows)
    logits, new = jax.jit(
        lambda p, v: m.apply(p, s, v, training=True))(p, ids)
    assert logits.shape == (2, 20, rows) and logits.dtype == jnp.float32
    close(logits, jax.jit(lambda p: ref.forward(CFG, share, p, ids))(p))
    counts = m.expert_counts(new)
    assert all(c["rows_overflow"] == 0 for c in counts)
    assert all(sum(c["rows_by_expert"]) == c["rows_held"] for c in counts)
    if share == (0, 1):
        assert [c["rows_held"] for c in counts] == [2 * 20 * 4] * 4

    def loss(p):
        return ref.cross_entropy(m.apply(p, s, ids, training=True)[0],
                                 targets)

    grads = jax.jit(jax.grad(loss))(p)
    # atol: as under test_gated_delta_net_against_reference
    close(grads, jax.jit(jax.grad(
        lambda p: ref.loss_fn(CFG, share, p, ids, targets)))(p), atol=1e-7)
    # every parameter learns, the router's through the gates
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert all(float(jnp.max(jnp.abs(g))) > 0 for _, g in flat), \
        [jax.tree_util.keystr(k) for k, g in flat
         if float(jnp.max(jnp.abs(g))) == 0]


def test_counts_that_do_not_split_are_refused():
    with pytest.raises(ValueError, match="do not split"):
        qwen3_next(CFG, (0, 3))
    with pytest.raises(ValueError, match="index outside"):
        qwen3_next(CFG, (8, 8))
    with pytest.raises(ValueError, match="vocab_share"):
        qwen3_next(CFG, vocab_share=(8, 8))
    with pytest.raises(ValueError, match="tie_word_embeddings=True is not"):
        qwen3_next(dict(CFG, tie_word_embeddings=True))


def test_the_published_share_counts_1028_million_parameters():
    """The benchmark's configuration at its published widths, by
    ``model.init``'s shapes (nothing drawn): 3 x 239.25 M + 232.79 M +
    77.79 M, as counted from the sizes."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    whole = dict(cfg, num_experts=cfg["published"]["num_experts"],
                 vocab_size=cfg["published"]["vocab_size"])
    m = qwen3_next(whole, (0, 8), vocab_share=(0, 8))
    params, _ = jax.eval_shape(m.init, key(0))
    size = lambda t: sum(int(np.prod(a.shape))
                         for a in jax.tree_util.tree_leaves(t))
    layers = [size(params["layers"][str(j)]) for j in range(4)]
    assert layers[:3] == [239_245_504] * 3 and layers[3] == 232_790_528
    assert size(params) == 1_028_320_320
    # the shared expert's gate is one column; every head is held
    assert params["layers"]["0"]["shared_gate"].shape == (2048, 1)
    assert params["layers"]["0"]["experts"]["w_in"].shape == (64, 2048, 1024)


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
    share, rows = (1, SHARES), 8
    tokens = np.random.default_rng(0).integers(
        0, rows, (2, 21)).astype(np.int32)
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(2)
    model = qwen3_next(CFG, share, vocab_share=share, q_block=8)
    _optimizer(model, ds, 0.5, 1).optimize()
    p0, _ = model.init(jax.random.split(key(3))[1])
    loss, want = ref.make_sgd_step(CFG, share)(
        jax.tree_util.tree_map(jnp.copy, p0), tokens[:, :-1],
        tokens[:, 1:], np.float32(0.5))
    assert abs(float(loss) - np.log(rows)) < 0.1
    change = jax.tree_util.tree_map(lambda a, b: a - b, model._params, p0)
    # the change is a difference of two close numbers, so it carries the
    # ulps of the weights themselves (a norm's weight of 1: 1.2e-7)
    close(change, jax.tree_util.tree_map(lambda a, b: a - b, want, p0),
          atol=2.5e-7)
    assert all(float(jnp.max(jnp.abs(c))) > 0
               for c in jax.tree_util.tree_leaves(change))


def test_trains_through_local_optimizer():
    """``LocalOptimizer.optimize()`` trains it as it trains granite and
    ZAYA: bf16 compute over f32 masters, the counters carried out as
    model state, the loss falling on a task that can be learned."""
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    rng = np.random.default_rng(0)
    start = rng.integers(0, 64, (32, 1))
    tokens = ((start + np.arange(17)) % 64).astype(np.int32)  # next = +1
    ds = DataSet.array([Sample(t[:-1], t[1:]) for t in tokens]) \
        >> SampleToMiniBatch(4)
    model = qwen3_next(CFG, q_block=8, row_factor=2.0)
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
    assert model.state_warnings(model._state) == []
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(model._params))
