"""Criterion unit tests (reference: per-criterion Specs in ``TEST/nn/``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.criterion import _pick_class


def test_class_nll():
    logp = jnp.log(jnp.array([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1]]))
    target = jnp.array([0, 1])
    loss = nn.ClassNLLCriterion().forward(logp, target)
    np.testing.assert_allclose(loss, -(np.log(0.7) + np.log(0.8)) / 2, rtol=1e-4)


def test_cross_entropy_equals_logsoftmax_plus_nll():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 5))
    target = jnp.array([0, 2, 4, 1])
    ce = nn.CrossEntropyCriterion().forward(logits, target)
    manual = nn.ClassNLLCriterion().forward(jax.nn.log_softmax(logits), target)
    np.testing.assert_allclose(ce, manual, rtol=1e-5)


def test_nll_ignore_index():
    logp = jnp.log(jnp.array([[0.5, 0.5], [0.9, 0.1]]))
    loss = nn.ClassNLLCriterion(ignore_index=-100).forward(
        logp, jnp.array([0, -100]))
    np.testing.assert_allclose(loss, -np.log(0.5), rtol=1e-5)


def test_mse():
    loss = nn.MSECriterion().forward(jnp.array([1.0, 2.0]), jnp.array([0.0, 0.0]))
    np.testing.assert_allclose(loss, 2.5)
    loss_sum = nn.MSECriterion(size_average=False).forward(
        jnp.array([1.0, 2.0]), jnp.array([0.0, 0.0]))
    np.testing.assert_allclose(loss_sum, 5.0)


def test_bce_matches_manual():
    x = jnp.array([0.8, 0.3])
    t = jnp.array([1.0, 0.0])
    loss = nn.BCECriterion().forward(x, t)
    np.testing.assert_allclose(loss, -(np.log(0.8) + np.log(0.7)) / 2, rtol=1e-5)


def test_bce_with_logits_matches_bce():
    logits = jnp.array([1.5, -0.5, 0.2])
    t = jnp.array([1.0, 0.0, 1.0])
    a = nn.BCEWithLogitsCriterion().forward(logits, t)
    b = nn.BCECriterion().forward(jax.nn.sigmoid(logits), t)
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_smooth_l1():
    loss = nn.SmoothL1Criterion().forward(jnp.array([0.5, 3.0]), jnp.array([0.0, 0.0]))
    np.testing.assert_allclose(loss, (0.5 * 0.25 + 2.5) / 2)


def test_margin():
    loss = nn.MarginCriterion().forward(jnp.array([0.5, 2.0]), jnp.array([1.0, 1.0]))
    np.testing.assert_allclose(loss, 0.25)


def test_kld_vae():
    mean = jnp.zeros((2, 3))
    log_var = jnp.zeros((2, 3))
    np.testing.assert_allclose(nn.KLDCriterion().forward((mean, log_var), None), 0.0)


def test_criterion_backward_is_grad():
    logits = jax.random.normal(jax.random.PRNGKey(1), (3, 4))
    target = jnp.array([0, 1, 2])
    c = nn.CrossEntropyCriterion()
    gi = c.backward(logits, target)
    assert gi.shape == logits.shape
    # gradient of mean-CE sums to ~0 per row minus one-hot/N
    np.testing.assert_allclose(jnp.sum(gi), 0.0, atol=1e-5)


def test_parallel_criterion():
    pc = nn.ParallelCriterion().add(nn.MSECriterion(), 0.5).add(nn.MSECriterion(), 1.0)
    x = (jnp.array([1.0]), jnp.array([2.0]))
    t = (jnp.array([0.0]), jnp.array([0.0]))
    np.testing.assert_allclose(pc.forward(x, t), 0.5 * 1.0 + 1.0 * 4.0)


def test_time_distributed_criterion():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 4))
    t = jnp.zeros((2, 5), dtype=jnp.int32)
    loss = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion()).forward(x, t)
    assert loss.shape == ()


def test_time_distributed_sum_inner_no_average():
    # inner sum-reducing criterion, size_average=False (default): plain sum
    x = jnp.ones((2, 3, 4))
    t = jnp.zeros((2, 3, 4))
    loss = nn.TimeDistributedCriterion(
        nn.MSECriterion(size_average=False)).forward(x, t)
    np.testing.assert_allclose(loss, 24.0)
    # size_average=True divides by timesteps
    loss_avg = nn.TimeDistributedCriterion(
        nn.MSECriterion(size_average=False), size_average=True).forward(x, t)
    np.testing.assert_allclose(loss_avg, 8.0)


def test_multilabel_margin_class_zero_with_padding():
    # single true class 0, padded with -1: perfect score -> zero loss
    x = jnp.array([[1.0, 0.0, 0.0]])
    t = jnp.array([[0, -1, -1]])
    loss = nn.MultiLabelMarginCriterion().forward(x, t)
    np.testing.assert_allclose(loss, 0.0)


# ------------------------------------------- the class pick, held to a gather


def _nll_by_gather(x, t, weights=None, size_average=True, logits=False,
                   ignore_index=-100):
    """``ClassNLLCriterion.apply`` with the pick as the gather it was."""
    logp = jax.nn.log_softmax(x, axis=-1) if logits else x
    valid = t != ignore_index
    t_safe = jnp.where(valid, t, 0)
    picked = jnp.take_along_axis(logp, t_safe[..., None], axis=-1)[..., 0]
    w = jnp.ones_like(picked) if weights is None \
        else jnp.take(weights, t_safe)
    w = jnp.where(valid, w, 0.0)
    total = -jnp.sum(w * picked)
    return total / jnp.maximum(jnp.sum(w), 1e-8) if size_average else total


def _logits(shape, seed):
    return 3.0 * jax.random.normal(jax.random.PRNGKey(seed), shape)


def _logp(shape, seed=0, dtype=jnp.float32):
    return jax.nn.log_softmax(_logits(shape, seed), axis=-1).astype(dtype)


_T5 = [0, 6, 3, 3, 1]
_PICK_CASES = {
    # name: () -> (input, target, ClassNLLCriterion arguments); built in
    # the test, so that importing this file computes nothing
    "rank1": lambda: (_logp((7,)), 4, {}),
    "rank2": lambda: (_logp((5, 7)), _T5, {}),
    "rank3": lambda: (_logp((2, 3, 7)), [[0, 6, 2], [5, 5, 1]], {}),
    "class_weights": lambda: (_logp((5, 7)), _T5,
                              {"weights": jnp.linspace(0.25, 2.0, 7)}),
    "ignore_index": lambda: (_logp((5, 7)), [0, -100, 3, -100, 1], {}),
    "ignore_index_weights_sum": lambda: (
        _logp((5, 7)), [2, -1, -1, 6, 0],
        {"weights": jnp.linspace(0.25, 2.0, 7), "size_average": False,
         "ignore_index": -1}),
    "sum": lambda: (_logp((5, 7)), _T5, {"size_average": False}),
    "logits": lambda: (_logits((5, 7), 3), _T5, {"logits": True}),
    "logits_rank3": lambda: (_logits((2, 3, 7), 4),
                             [[0, -100, 2], [5, 5, 1]], {"logits": True}),
    "bf16": lambda: (_logp((5, 7), dtype=jnp.bfloat16), _T5, {}),
    "bf16_logits": lambda: (_logits((5, 7), 5).astype(jnp.bfloat16), _T5,
                            {"logits": True}),
    "neg_inf_elsewhere": lambda: (
        _logp((5, 7)).at[1, 2].set(-jnp.inf), _T5, {}),
    "neg_inf_logit_elsewhere": lambda: (
        _logits((5, 7), 6).at[1, 2].set(-jnp.inf), _T5, {"logits": True}),
}


@pytest.mark.parametrize("case", list(_PICK_CASES))
def test_class_pick_equals_the_gather_it_replaced(case):
    """``_pick_class`` is a masked row sum where a ``take_along_axis``
    was: the loss and its gradient keep every bit, in the input's
    dtype, and what an unpicked column holds reaches neither."""
    x, t, kw = _PICK_CASES[case]()
    t = jnp.array(t)
    crit = nn.ClassNLLCriterion(**kw)
    got, got_g = jax.value_and_grad(crit.apply)(x, t)
    want, want_g = jax.value_and_grad(_nll_by_gather)(x, t, **kw)
    assert got.dtype == want.dtype and got_g.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got_g, np.float32),
                                  np.asarray(want_g, np.float32))
    assert np.isfinite(np.asarray(got, np.float32))
    if "neg_inf" in case:
        assert np.asarray(got_g)[1, 2] == 0.0


@pytest.mark.parametrize("axis,shape", [(1, (2, 5, 3, 4)), (0, (5, 6)),
                                        (-1, (6, 5)), (-2, (2, 5, 3))])
def test_class_pick_along_any_axis(axis, shape):
    logp = _logp(shape, seed=7)
    t_shape = tuple(np.delete(shape, axis))
    t = jax.random.randint(jax.random.PRNGKey(8), t_shape, 0, shape[axis])
    gather = lambda a: jnp.squeeze(  # noqa: E731
        jnp.take_along_axis(a, jnp.expand_dims(t, axis), axis=axis), axis)
    np.testing.assert_array_equal(_pick_class(logp, t, axis), gather(logp))
    np.testing.assert_array_equal(
        jax.grad(lambda a: jnp.sum(_pick_class(a, t, axis) ** 2))(logp),
        jax.grad(lambda a: jnp.sum(gather(a) ** 2))(logp))


def test_class_pick_outside_the_classes_picks_nothing():
    # the one place where the row sum and the gather differ: the gather
    # wrapped a negative target and read NaN past the end
    logp = _logp((3, 7))
    np.testing.assert_array_equal(
        _pick_class(logp, jnp.array([-1, 7, 2])),
        jnp.array([0.0, 0.0, logp[2, 2]]))


# ----------------------------------------------------------- round-2 breadth


def test_cosine_distance_criterion():
    x = jnp.array([[1.0, 0.0], [0.0, 2.0]])
    # identical directions -> 0; orthogonal -> 1
    np.testing.assert_allclose(
        nn.CosineDistanceCriterion().forward(x, x), 0.0, atol=1e-6)
    y = jnp.array([[0.0, 1.0], [2.0, 0.0]])
    np.testing.assert_allclose(
        nn.CosineDistanceCriterion().forward(x, y), 1.0, atol=1e-6)


def test_cosine_proximity_matches_torch():
    import torch
    rng = np.random.RandomState(0)
    x = rng.randn(4, 6).astype(np.float32)
    y = rng.randn(4, 6).astype(np.float32)
    ours = nn.CosineProximityCriterion().forward(jnp.asarray(x),
                                                 jnp.asarray(y))
    ref = -torch.nn.functional.cosine_similarity(
        torch.tensor(x), torch.tensor(y)).mean().item()
    np.testing.assert_allclose(float(ours), ref, rtol=1e-5)


def test_dot_product_criterion_grad_is_target():
    x = jnp.array([[1.0, 2.0], [3.0, 4.0]])
    t = jnp.array([[0.5, 0.5], [1.0, -1.0]])
    c = nn.DotProductCriterion()
    np.testing.assert_allclose(c.forward(x, t), float(np.sum(x * t)),
                               rtol=1e-6)
    np.testing.assert_allclose(c.backward(x, t), t, rtol=1e-6)


def test_kld_probability_form():
    p = jnp.array([[0.5, 0.5]])
    q = jnp.array([[0.25, 0.75]])
    # KL(target||input): target=p, input=q
    expected = float(np.sum(p * np.log(p / q)))
    np.testing.assert_allclose(
        nn.KullbackLeiblerDivergenceCriterion().forward(q, p), expected,
        rtol=1e-5)


def test_l1_hinge_embedding():
    x1 = jnp.array([[1.0, 1.0]])
    x2 = jnp.array([[0.0, 0.0]])
    c = nn.L1HingeEmbeddingCriterion(margin=3.0)
    np.testing.assert_allclose(c.forward((x1, x2), jnp.array([1])), 2.0)
    np.testing.assert_allclose(c.forward((x1, x2), jnp.array([-1])), 1.0)


def test_mape_msle_poisson():
    t = jnp.array([[2.0, 4.0]])
    x = jnp.array([[1.0, 5.0]])
    np.testing.assert_allclose(
        nn.MeanAbsolutePercentageCriterion().forward(x, t),
        100.0 * (0.5 + 0.25) / 2, rtol=1e-5)
    np.testing.assert_allclose(
        nn.MeanSquaredLogarithmicCriterion().forward(x, t),
        np.mean((np.log([2.0, 6.0]) - np.log([3.0, 5.0])) ** 2), rtol=1e-5)
    np.testing.assert_allclose(
        nn.PoissonCriterion().forward(x, t),
        np.mean([1.0 - 2.0 * np.log(1.0), 5.0 - 4.0 * np.log(5.0)]),
        rtol=1e-5)


def test_multi_margin_matches_torch():
    import torch
    rng = np.random.RandomState(1)
    x = rng.randn(5, 7).astype(np.float32)
    y = rng.randint(0, 7, size=5)
    for p in (1, 2):
        ours = nn.MultiMarginCriterion(p=p).forward(
            jnp.asarray(x), jnp.asarray(y))
        ref = torch.nn.MultiMarginLoss(p=p)(
            torch.tensor(x), torch.tensor(y)).item()
        np.testing.assert_allclose(float(ours), ref, rtol=1e-5)


def test_class_simplex_properties():
    c = nn.ClassSimplexCriterion(5)
    s = np.asarray(c.simplex)
    # vertices unit-norm, mutual dot products all equal
    np.testing.assert_allclose(np.linalg.norm(s, axis=1), 1.0, atol=1e-5)
    dots = s @ s.T
    off = dots[~np.eye(5, dtype=bool)]
    np.testing.assert_allclose(off, off[0], atol=1e-5)
    # loss is zero when input == embedding
    t = jnp.array([0, 3])
    emb = jnp.zeros((2, 5)).at[:, :4].set(jnp.asarray(s[np.array([0, 3])]))
    np.testing.assert_allclose(c.forward(emb, t), 0.0, atol=1e-10)


def test_smooth_l1_with_weights():
    sigma = 2.0
    x = jnp.array([[0.1, 2.0]])
    gt = jnp.array([[0.0, 0.0]])
    w_in = jnp.array([[1.0, 1.0]])
    w_out = jnp.array([[2.0, 0.5]])
    c = nn.SmoothL1CriterionWithWeights(sigma=sigma)
    # |0.1| < 1/4 -> quad: 0.5*4*0.01 = 0.02 * w_out 2 = 0.04
    # |2| >= 1/4 -> lin: 2 - 0.125 = 1.875 * 0.5 = 0.9375
    np.testing.assert_allclose(
        c.forward(x, (gt, w_in, w_out)), 0.04 + 0.9375, rtol=1e-5)


def test_time_distributed_mask():
    # (N=1, T=3, C=2) log-probs, last step padded (target 0 = padding)
    logp = jnp.log(jnp.array([[[0.9, 0.1], [0.2, 0.8], [0.5, 0.5]]]))
    tgt = jnp.array([[1, 1, 0]])
    c = nn.TimeDistributedMaskCriterion(nn.ClassNLLCriterion(),
                                        padding_value=0)
    expected = -(np.log(0.1) + np.log(0.8)) / 2
    np.testing.assert_allclose(c.forward(logp, tgt), expected, rtol=1e-5)


def test_transformer_criterion():
    double = nn.Lambda(lambda x: 2.0 * x)
    c = nn.TransformerCriterion(nn.MSECriterion(),
                                input_transformer=double,
                                target_transformer=double)
    x = jnp.array([[1.0, 2.0]])
    t = jnp.array([[0.0, 0.0]])
    np.testing.assert_allclose(c.forward(x, t), 4.0 * 2.5, rtol=1e-6)
