"""The plain reference of the decode cell against the program at the
``tiny`` size on the CPU: the full forward, prefill then decode through
the cache, the comparison's control (the reference in bfloat16) and a
cache position shifted by one."""

import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402

SEEDS = (3, 2 ** 31 + 5, 77)


@functools.lru_cache(maxsize=None)
def _tiny():
    """The tiny configuration, its model and its weights for each seed,
    built once a process (a plain helper and no fixture: tier-1 collects
    these functions through ``tests/test_benchmark_guards.py``, which
    takes the ``test_*`` names of this module and nothing else)."""
    import jax
    cfg = lib.with_tiny(lib.load_json("configs", "gpt2-xl"), True)
    builder = lib.load_module("builders", "transformer_lm")
    model = builder.build_model(cfg)
    out = {"cfg": cfg, "builder": builder, "model": model, "seeds": {}}
    for seed in SEEDS:
        w = builder.draw_weights(cfg, seed)
        out["seeds"][seed] = (w, builder.product_params(cfg, model, w))
    out["jax"] = jax
    return out


def _decode(tiny, params, prompts, n_new, shift=0):
    """Greedy prefill-then-decode through the product's own functions,
    every slot occupied; ``shift`` moves the decode step's cache
    position.  Returns rows of prompt + answer."""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer import (init_kv_cache,
                                              transformer_lm_decode_step,
                                              transformer_lm_prefill)
    model, cfg = tiny["model"], tiny["cfg"]
    slots, t_max = len(prompts), cfg["serve"]["max_seq_len"]
    k, v = init_kv_cache(model, slots, t_max)
    last, lengths = [], []
    for s, prompt in enumerate(prompts):
        lp, kp, vp = transformer_lm_prefill(model, params,
                                            jnp.asarray(prompt)[None])
        k = k.at[:, s:s + 1, :, :len(prompt)].set(kp)
        v = v.at[:, s:s + 1, :, :len(prompt)].set(vp)
        last.append(int(np.asarray(lp)[0, -1].argmax()))
        lengths.append(len(prompt))
    answers = [[t] for t in last]
    for _ in range(n_new - 1):
        lp, k, v = transformer_lm_decode_step(
            model, params, jnp.asarray(last, jnp.int32),
            jnp.asarray(lengths, jnp.int32) + shift, k, v)
        last = [int(row.argmax()) for row in np.asarray(lp)]
        lengths = [n + 1 for n in lengths]
        for a, t in zip(answers, last):
            a.append(t)
    rows = np.zeros((slots, t_max), np.int32)
    spans = []
    for s, (prompt, a) in enumerate(zip(prompts, answers)):
        rows[s, :len(prompt)] = prompt
        rows[s, len(prompt):len(prompt) + len(a)] = a
        spans.append((len(prompt), len(prompt) + len(a)))
    return rows, spans


def _prompts(seed, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32)
            for n in (4, 11, 23, 40)]


def _answer_gaps(g, spans):
    return np.concatenate([g[r, p - 1:e - 1]
                           for r, (p, e) in enumerate(spans)])


def test_reference_forward_is_the_models_forward():
    tiny = _tiny()
    cfg, seed = tiny["cfg"], SEEDS[0]
    w, params = tiny["seeds"][seed]
    ref = lib.load_module("references", "transformer_lm")
    tokens = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 33)).astype(np.int32)
    want = np.asarray(ref.log_probs(cfg, w, tokens))
    _p, state = tiny["model"].init(tiny["jax"].random.PRNGKey(0))
    got, _ = tiny["model"].apply(params, state, tokens)
    assert np.allclose(np.asarray(got), want, atol=2e-5)
    assert ref.parameter_count(cfg) == sum(
        a.size for a in tiny["jax"].tree_util.tree_leaves(w))


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_then_decode_agrees_and_the_control_does_not(seed):
    tiny = _tiny()
    cfg, b = tiny["cfg"], tiny["builder"]
    import jax.numpy as jnp
    w, params = tiny["seeds"][seed]
    limit, mean_limit = (cfg["serve"]["gap_limit"],
                         cfg["serve"]["mean_gap_limit"])
    rows, spans = _decode(tiny, params, _prompts(seed, cfg["vocab_size"]),
                          n_new=16)
    served, picked = b.served_gaps(cfg, w, rows, lower=jnp.bfloat16)
    served, picked = _answer_gaps(served, spans), _answer_gaps(picked, spans)
    assert served.size == 64
    # the program's tokens are the reference's best, up to f32 rounding
    assert served.max() <= limit / 10 and served.mean() <= mean_limit / 10
    # the reference in bfloat16 in the program's place is NOT correct
    assert picked.max() > 3 * limit and picked.mean() > mean_limit


@pytest.mark.parametrize("seed", SEEDS)
def test_cache_position_shifted_by_one_fails_the_gap_limit(seed):
    tiny = _tiny()
    cfg, b = tiny["cfg"], tiny["builder"]
    w, params = tiny["seeds"][seed]
    rows, spans = _decode(tiny, params, _prompts(seed, cfg["vocab_size"]),
                          n_new=16, shift=1)
    served, _ = b.served_gaps(cfg, w, rows)
    gaps = _answer_gaps(served, spans)
    assert gaps.max() > 3 * cfg["serve"]["gap_limit"]
    assert gaps.mean() > 3 * cfg["serve"]["mean_gap_limit"]
