"""The plain reference a train cell is checked against, independent of
the driver under test: one jitted ``value_and_grad`` of ``model.apply``
+ criterion at the per-chip batch, and SGD written out in ``jax.numpy``.

Departures from "float32, highest precision", each forced and noted:
the forward and backward run in the configuration's own compute dtype
(ResNet-50 at batch 256 in float32 at full precision does not fit one
chip's 16 GB next to nothing else, and the cell states bf16 compute),
with float32 master parameters, float32 criterion and float32 update —
the mixed-precision contract written plainly here, not imported."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

tmap = jax.tree_util.tree_map


def _cast(tree, dtype):
    if dtype is None:
        return tree
    return tmap(lambda a: a.astype(dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def make_grad_fn(model, criterion, compute_dtype):
    """``(params, mstate, x, y) -> ((loss, new_mstate), grads)``, one
    program for one per-chip batch."""

    def loss_fn(params, mstate, x, y):
        out, new_state = model.apply(_cast(params, compute_dtype), mstate,
                                     _cast(x, compute_dtype),
                                     training=True,
                                     rng=jax.random.PRNGKey(0))
        out = _cast(out, jnp.float32)
        return criterion.apply(out, y), _cast(new_state, jnp.float32)

    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))


def make_sgd(lr, momentum=0.0, weight_decay=0.0, dampening=None):
    """Torch-style SGD as the product's ``optim.SGD`` defines it:
    ``g += wd * p``; ``v = mu * v + (1 - dampening) * g`` with dampening
    defaulting to the momentum; ``p -= lr * v``.  One jitted update."""
    damp = momentum if dampening is None else dampening

    @jax.jit
    def update(params, velocity, grads):
        if weight_decay:
            grads = tmap(lambda g, p: g + weight_decay * p, grads, params)
        if momentum == 0.0:
            return tmap(lambda p, g: p - lr * g, params, grads), velocity
        velocity = tmap(lambda v, g: momentum * v + (1 - damp) * g,
                        velocity, grads)
        return tmap(lambda p, v: p - lr * v, params, velocity), velocity

    return update


def reference_losses(model, criterion, params, mstate, batches, *,
                     shards: int, compute_dtype, sgd: dict) -> list:
    """Losses of plain data-parallel SGD over ``batches`` (each a global
    ``(x, y)`` pair of host arrays).  With ``shards`` > 1 the SAME
    per-chip program runs over the shards of a global batch in turn,
    the gradients are averaged and one update is made: data-parallel
    SGD with per-shard BatchNorm statistics, written plainly."""
    grad_fn = make_grad_fn(model, criterion, compute_dtype)
    update = make_sgd(**sgd)
    mean = jax.jit(lambda trees: tmap(lambda *g: sum(g) / len(g), *trees))
    params = tmap(jnp.array, params)
    velocity = tmap(jnp.zeros_like, params)
    losses = []
    for x, y in batches:
        xs, ys = np.split(x, shards), np.split(y, shards)
        shard_losses, shard_grads = [], []
        for xi, yi in zip(xs, ys):
            (loss, new_state), grads = grad_fn(params, mstate, xi, yi)
            shard_losses.append(loss)
            shard_grads.append(grads)
        # the last shard's running statistics go forward; a training
        # forward pass never reads them, so no loss depends on them
        mstate = new_state
        grads = shard_grads[0] if shards == 1 else mean(shard_grads)
        del shard_grads
        params, velocity = update(params, velocity, grads)
        losses.append(float(np.mean([float(v) for v in shard_losses])))
    return losses


def loss_diff(losses, ref) -> float:
    """Largest per-step relative difference of two loss curves."""
    return float(np.max(np.abs(np.subtract(losses, ref)) / np.abs(ref)))
