"""Wide&Deep recommendation training.

Analog of the reference's Wide&Deep workload (named in BASELINE.json;
reference-era BigDL serves it via the sparse layer family —
``SparseLinear``/``LookupTableSparse``).  Trains on MovieLens-style
implicit feedback: wide = crossed (user x genre-bucket) sparse
features through SparseLinear, deep = user/item embeddings through an
MLP.

Two wide-feature representations (see ``nn/sparse.py``):
- default: fixed-width id bags (ids + weights arrays);
- ``--sparse-coo``: ragged per-sample sparse features collated into
  batch-COO ``SparseMiniBatch``es (the reference's ``SparseMiniBatch``
  path) executed via segment-sum kernels.
"""

from __future__ import annotations

import argparse

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser(description="Train Wide&Deep on ratings")
    p.add_argument("-f", "--folder", default=None,
                   help="MovieLens dir with ratings.dat (default: "
                        "synthetic ratings)")
    p.add_argument("-b", "--batch-size", type=int, default=256)
    p.add_argument("-e", "--max-epoch", type=int, default=8)
    p.add_argument("--sparse-coo", action="store_true",
                   help="feed the wide part as batch-COO "
                        "SparseMiniBatches instead of fixed-width bags")
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
    from bigdl_tpu.dataset import movielens
    from bigdl_tpu.models.recommender import WideAndDeep

    if args.folder:
        ratings = movielens.load(args.folder)
    else:
        ratings = movielens.synthetic_ratings(n_users=100, n_items=80,
                                              n_ratings=6000)
    users = ratings[:, 0] - 1
    items = ratings[:, 1] - 1
    labels = (ratings[:, 2] >= 4).astype(np.float32)
    n_users = int(users.max()) + 1
    n_items = int(items.max()) + 1

    # wide part: crossed (user, item-bucket) feature ids as 1-hot id bags
    n_buckets = 8
    wide_dim = n_users * n_buckets
    wide_ids = (users * n_buckets + items % n_buckets).astype(np.int32)
    wide_bags = wide_ids[:, None]                  # (N, 1) id bag
    wide_weights = np.ones_like(wide_bags, np.float32)

    model = WideAndDeep(wide_dim=wide_dim,
                        deep_field_counts=[n_users, n_items],
                        embed_dim=16, hidden=(64, 32))
    params, state = model.init(jax.random.PRNGKey(0))

    deep_ids = np.stack([users, items], axis=1).astype(np.int32)
    N = len(labels)

    def loss_fn(p, batch_ix):
        wide_in = (jnp.asarray(wide_bags)[batch_ix],
                   jnp.asarray(wide_weights)[batch_ix])
        out, _ = model.apply(p, state,
                             (wide_in, jnp.asarray(deep_ids)[batch_ix],
                              None))
        pred = out[:, 0]
        yb = jnp.asarray(labels)[batch_ix]
        eps = 1e-7
        return -jnp.mean(yb * jnp.log(pred + eps)
                         + (1 - yb) * jnp.log(1 - pred + eps))

    method = optim.Adam(learning_rate=0.01)
    ostate = method.init_state(params)
    rng = np.random.default_rng(0)
    it = 0
    if args.sparse_coo:
        # ragged sparse wide features -> batch-COO SparseMiniBatch
        from bigdl_tpu.dataset import SparseSample, batch_sparse_samples
        samples = [SparseSample([wide_ids[i]], [1.0], wide_dim,
                                dense=[deep_ids[i]], label=labels[i])
                   for i in range(N)]

        @jax.jit
        def coo_step(p, os_, coo, dids, yb, it):
            def lf(p):
                out, _ = model.apply(p, state, (coo, dids, None))
                pred = out[:, 0]
                eps = 1e-7
                return -jnp.mean(yb * jnp.log(pred + eps)
                                 + (1 - yb) * jnp.log(1 - pred + eps))
            loss, g = jax.value_and_grad(lf)(p)
            p, os_ = method.update(g, p, os_, 0.01, it)
            return p, os_, loss

        for epoch in range(args.max_epoch):
            perm = rng.permutation(N)
            for s in range(0, N - args.batch_size + 1, args.batch_size):
                mb = batch_sparse_samples(
                    [samples[i] for i in perm[s:s + args.batch_size]],
                    nnz_buckets=[args.batch_size])
                coo, dids = mb.input
                params, ostate, loss = coo_step(
                    params, ostate, coo, jnp.asarray(dids),
                    jnp.asarray(mb.target), it)
                it += 1
    else:
        step = jax.jit(jax.value_and_grad(loss_fn))
        update = jax.jit(method.update)
        for epoch in range(args.max_epoch):
            perm = rng.permutation(N)
            for s in range(0, N - args.batch_size + 1, args.batch_size):
                ix = jnp.asarray(perm[s:s + args.batch_size])
                loss, g = step(params, ix)
                params, ostate = update(g, params, ostate, 0.01, it)
                it += 1
    # training AUC-ish: accuracy at 0.5
    all_ix = jnp.arange(N)
    wide_in = (jnp.asarray(wide_bags), jnp.asarray(wide_weights))
    out, _ = model.apply(params, state,
                         (wide_in, jnp.asarray(deep_ids), None))
    acc = float(((np.asarray(out[:, 0]) > 0.5) == labels).mean())
    print(f"final: loss={float(loss):.4f} train_acc={acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
