"""Constituency TreeLSTM sentiment classification.

Mirror of the reference ``DL/example/treeLSTMSentiment/`` (BinaryTreeLSTM
on SST parse trees).  Runs on synthetic right-leaning parse trees whose
sentiment is determined by the leaf vocabulary, so the tree composition
has real signal to learn.
"""

from __future__ import annotations

import argparse

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def synthetic_trees(n=256, n_leaves=6, vocab=40, seed=0):
    """Right-leaning binary trees; label = majority leaf polarity."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_nodes = 2 * n_leaves - 1
    # node rows [left, right, leaf_ix], 1-based, children before parents
    tree = np.zeros((n_nodes, 3), np.float32)
    for i in range(n_leaves):
        tree[i] = [0, 0, i + 1]
    nxt = n_leaves
    prev = n_leaves  # node id of rightmost leaf (1-based)
    # compose leaves right-to-left: (l5,(l4,(l3,...)))
    for k in range(n_leaves - 1):
        li = n_leaves - 1 - k  # leaf id to the left
        tree[nxt] = [li, prev, 0]
        prev = nxt + 1
        nxt += 1
    tokens = rng.integers(0, vocab, (n, n_leaves))
    labels = (np.where(tokens < vocab // 2, 1, -1).sum(1) > 0).astype(
        np.int32)
    return tokens, np.tile(tree[None], (n, 1, 1)), labels, n_nodes


def main():
    p = argparse.ArgumentParser()
    p.add_argument("-e", "--max-epoch", type=int, default=60)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--embed-dim", type=int, default=16)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn, optim

    vocab = 40
    tokens, trees, labels, n_nodes = synthetic_trees(vocab=vocab)
    embed = nn.LookupTable(vocab, args.embed_dim)
    tree_lstm = nn.BinaryTreeLSTM(args.embed_dim, args.hidden)
    head = nn.Linear(args.hidden, 2)

    ek, tk, hk = jax.random.split(jax.random.PRNGKey(0), 3)
    e_p, _ = embed.init(ek)
    t_p, _ = tree_lstm.init(tk)
    h_p, _ = head.init(hk)
    params = {"embed": e_p, "tree": t_p, "head": h_p}

    xs = jnp.asarray(tokens)
    ts = jnp.asarray(trees)
    ys = jnp.asarray(labels)

    def loss_fn(p):
        emb, _ = embed.apply(p["embed"], {}, xs)
        states, _ = tree_lstm.apply(p["tree"], {}, (emb, ts))
        root = states[:, -1]  # root is the last (topologically) node
        logits, _ = head.apply(p["head"], {}, root)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, ys[:, None], 1))

    step = jax.jit(jax.value_and_grad(loss_fn))
    method = optim.Adam(learning_rate=0.02)
    opt_state = method.init_state(params)
    update = jax.jit(method.update)  # one wrapper: compile once
    for i in range(args.max_epoch):
        loss, g = step(params)
        params, opt_state = update(g, params, opt_state, 0.02, i)
    emb, _ = embed.apply(params["embed"], {}, xs)
    states, _ = tree_lstm.apply(params["tree"], {}, (emb, ts))
    logits, _ = head.apply(params["head"], {}, states[:, -1])
    acc = float((jnp.argmax(logits, -1) == ys).mean())
    print(f"final: loss={float(loss):.4f} train_acc={acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
