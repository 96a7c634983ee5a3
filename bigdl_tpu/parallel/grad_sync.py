"""Compressed, bucketed gradient synchronization — the TPU-native
``AllReduceParameter``.

Reference: ``DL/parameters/AllReduceParameter.scala`` +
``FP16CompressedTensor.scala``.  Each Spark iteration, every node (1)
fetches the FP16-compressed gradient partitions of its owned 1/N slice
of the flat parameter vector, (2) aggregates them and runs the
optimizer on that slice only, and (3) re-publishes the updated slice in
the FP16 wire format for the next forward.  That protocol IS a
reduce-scatter (+ sharded update) + all-gather with a compressed wire
dtype — see also "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arXiv:2004.13336), the same design expressed
in XLA terms.

The first TPU port dropped the wire format on the assumption that ICI
makes software compression unnecessary; BENCH r05 then measured
``collective_overhead_fraction = 0.32`` at 8 chips — gradient sync, not
compute, was the biggest gap.  This module brings the explicit protocol
back, natively:

- gradients are flattened into **size-capped buckets**
  (``Config.grad_bucket_bytes``) so XLA's latency-hiding scheduler can
  overlap per-bucket collectives with backward compute instead of
  waiting for one monolithic fused all-reduce;
- each bucket is **downcast to the wire dtype**
  (``Config.grad_wire_dtype``: f32 | bf16 | f16) with the shared
  unbiased rounding (``utils.precision.stochastic_round`` — the same
  helper behind SGD's reduced-precision momentum), then
  ``lax.psum_scatter`` over the ``data`` axis hands every chip its
  owned 1/N slice, upcast to f32;
- the optimizer update runs on the **f32 master slice** each chip owns
  (``gs_state["master"]``) — ZeRO-1 exactly, subsuming the old
  constraint-only sharded-state path;
- updated slices are downcast to the wire dtype and ``lax.all_gather``-ed
  back into the replicated f32 param pytree used by the next
  forward/backward (the analog of the reference's FP16 weight
  re-publish: with a sub-f32 wire the replicated params carry wire
  precision, the per-chip masters stay exact f32).

Everything runs inside ``shard_map`` within the fused K-step jit built
by ``DistriOptimizer._build_block_fn``; this module holds the pure
per-chip math plus the host-side bucket planning.

Semantics vs the GSPMD auto-collective path (documented divergences,
all shared with the reference's per-executor training):
- the loss reported is the pmean of per-chip local-batch means
  (identical for equal shard sizes, up to float association);
- batch-statistics layers (BatchNorm) see their LOCAL batch shard; the
  new model state is pmean-synced across chips after the step (the
  reference computes per-partition statistics the same way);
- dropout draws the same per-step key on every chip, applied to that
  chip's batch shard.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from bigdl_tpu.utils.precision import stochastic_round

tmap = jax.tree_util.tree_map

# wire-dtype knob values (Config.grad_wire_dtype / DistriOptimizer
# grad_wire_dtype=...); f32 is the identity wire — bitwise-equal to a
# plain psum, gated by tests/test_grad_sync.py
WIRE_DTYPES = {
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f16": jnp.float16, "float16": jnp.float16,
}

# base key for the wire downcast noise; per-(step, bucket) keys are
# folded in so no two downcasts in a block share noise
_WIRE_KEY_SALT = 0x77e1


def resolve_wire_dtype(name) -> Any:
    """``"bf16"``/``"f32"``/``"f16"`` (or a jnp dtype) → jnp dtype."""
    if not isinstance(name, str):
        return jnp.dtype(name).type if name is not None else jnp.float32
    try:
        return WIRE_DTYPES[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown grad wire dtype {name!r}; expected one of "
            f"{sorted(set(WIRE_DTYPES))}") from None


class BucketPlan:
    """Static flattening plan: which param leaves land in which bucket,
    at what offset, and how much tail padding makes each bucket divide
    evenly over the ``data`` axis.  Built once per run on the host —
    everything jit-traced closes over it as a constant."""

    __slots__ = ("n_shard", "leaf_meta", "buckets", "bucket_sizes",
                 "treedef")

    def __init__(self, n_shard: int, leaf_meta, buckets, bucket_sizes,
                 treedef):
        self.n_shard = n_shard
        self.leaf_meta = leaf_meta        # [(shape, size, dtype)]
        self.buckets = buckets            # [[leaf index, ...], ...]
        self.bucket_sizes = bucket_sizes  # padded, % n_shard == 0
        self.treedef = treedef

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def slice_size(self, b: int) -> int:
        return self.bucket_sizes[b] // self.n_shard


def build_plan(params, n_shard: int, bucket_bytes: int) -> BucketPlan:
    """Greedy size-capped bucketing in leaf order.  A leaf larger than
    the cap gets a bucket of its own (never split — slicing a single
    leaf across buckets would complicate unflattening for no overlap
    benefit: one oversized bucket is already one collective)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if not leaves:
        raise ValueError("grad_sync: model has no parameters")
    leaf_meta = [(tuple(l.shape), int(np.prod(l.shape, dtype=np.int64)),
                  jnp.dtype(l.dtype)) for l in leaves]
    cap = max(1, int(bucket_bytes) // 4)  # f32 elements per bucket
    buckets: List[List[int]] = []
    sizes: List[int] = []
    cur: List[int] = []
    cur_n = 0
    for i, (_, size, _) in enumerate(leaf_meta):
        # bucketing is a pure function of the param tree and
        # grad_bucket_bytes — every host derives the identical plan
        # (and therefore the identical collective schedule)
        # replicated-by: plan-from-config
        if cur and cur_n + size > cap:
            buckets.append(cur)
            sizes.append(cur_n)
            cur, cur_n = [], 0
        cur.append(i)
        cur_n += size
    buckets.append(cur)
    sizes.append(cur_n)
    padded = [-(-s // n_shard) * n_shard for s in sizes]
    return BucketPlan(n_shard, leaf_meta, buckets, padded, treedef)


def flatten_to_buckets(plan: BucketPlan, tree) -> List[jnp.ndarray]:
    """Pytree → list of padded flat f32 buckets (leaf order, zeros in
    the tail padding)."""
    leaves = jax.tree_util.tree_leaves(tree)
    out = []
    for b, idxs in enumerate(plan.buckets):
        flat = jnp.concatenate(
            [leaves[i].reshape(-1).astype(jnp.float32) for i in idxs])
        pad = plan.bucket_sizes[b] - flat.shape[0]
        if pad:  # replicated-by: plan-from-config
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        out.append(flat)
    return out


def unflatten_from_buckets(plan: BucketPlan, buckets: Sequence):
    """Inverse of :func:`flatten_to_buckets` — original shapes/dtypes."""
    leaves: List[Optional[jnp.ndarray]] = [None] * len(plan.leaf_meta)
    for b, idxs in enumerate(plan.buckets):
        off = 0
        flat = buckets[b]
        for i in idxs:
            shape, size, dtype = plan.leaf_meta[i]
            leaves[i] = lax.slice(flat, (off,), (off + size,)) \
                .reshape(shape).astype(dtype)
            off += size
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def init_state(plan: BucketPlan, params, optim_method) -> dict:
    """Build the grad_sync optimizer-state pytree: f32 master buckets
    (the full flat vectors — placing them with a ``P("data")`` sharding
    gives each chip exactly its owned slice) plus the wrapped
    optimizer's own state over those buckets.

    Only elementwise (tree-map-shaped) optimizers qualify: each inner
    state leaf must mirror a master bucket leaf-for-leaf so the
    host-built full-bucket state shards into per-chip slice state.
    L-BFGS (flat history matrices) does not — it needs the full
    vector on every chip."""
    masters = flatten_to_buckets(plan, params)
    inner = optim_method.init_state(masters)
    master_shapes = {m.shape for m in masters}
    for leaf in jax.tree_util.tree_leaves(inner):
        # model structure is identical on every host — the refusal (or
        # not) is uniform  # replicated-by: model-structure
        if leaf.shape not in master_shapes:
            raise ValueError(
                f"grad_sync requires an elementwise optimizer whose "
                f"state leaves mirror the parameter buckets; "
                f"{type(optim_method).__name__} created a "
                f"{leaf.shape}-shaped state leaf (buckets: "
                f"{sorted(master_shapes)}).  Use parameter_sharding="
                f"False/grad_sync=False for this method.")
    return {"master": masters, "opt": inner}


def bucket_content_sizes(plan: BucketPlan) -> List[int]:
    """Unpadded element count of each bucket — a pure function of the
    param tree and ``grad_bucket_bytes``, INVARIANT under the world
    size (only the tail padding divides by ``n_shard``).  This is the
    quantity elastic resume compares across snapshots: two plans with
    equal content layouts hold the same logical values, however they
    were padded."""
    return [sum(plan.leaf_meta[i][1] for i in idxs)
            for idxs in plan.buckets]


def reshard_state(plan: BucketPlan, gs_state: dict) -> dict:
    """Re-pad a grad_sync optimizer state for a NEW world size
    (elastic resume).  Runs on the host against the freshly-restored
    state: every array leaf of ``gs_state`` is a padded flat bucket
    (masters and elementwise inner state alike — ``init_state``
    enforces the mirror), identified by the trailing list index of its
    tree path.  Padding carries no information (``flatten_to_buckets``
    zero-fills, elementwise optimizers map zeros to zeros), so
    resharding is: slice each bucket to its content, re-pad with zeros
    to ``plan.bucket_sizes``.  Gradient sums are world-size-invariant,
    making the resharded trajectory exact at the replay boundary."""
    content = bucket_content_sizes(plan)

    def _bucket_ix(path) -> int:
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.SequenceKey):
                return entry.idx
        key = jax.tree_util.keystr(path)
        raise ValueError(
            f"grad_sync reshard: state leaf at {key} has no bucket "
            f"index — not a grad_sync state layout")

    def _repad(path, leaf):
        b = _bucket_ix(path)
        if b >= len(content):  # replicated-by: plan-from-config
            raise ValueError(
                f"grad_sync reshard: state has a bucket #{b} but the "
                f"new plan only has {plan.num_buckets} — param tree or "
                f"grad_bucket_bytes changed, not just the world size")
        arr = np.asarray(leaf)
        # every host restored the same snapshot — its bucket layout is
        # uniform  # replicated-by: snapshot-schema
        if arr.ndim != 1 or arr.shape[0] < content[b]:
            raise ValueError(
                f"grad_sync reshard: bucket #{b} holds "
                f"{arr.shape} elements but the plan needs "
                f"{content[b]} — param tree or grad_bucket_bytes "
                f"changed, not just the world size")
        out = np.zeros((plan.bucket_sizes[b],), dtype=arr.dtype)
        out[:content[b]] = arr[:content[b]]
        return out

    return jax.tree_util.tree_map_with_path(_repad, gs_state)


def wire_cast(x, wire_dtype, key, n_sum: int = 1):
    """Downcast one bucket to the wire dtype with the shared unbiased
    rounding (no-op for the f32 wire).  The f16 wire SATURATES first:
    unlike bf16 (f32 exponent range, no loss scaling needed), an f16
    wire can overflow to inf and poison the masters with NaN via the
    psum.  ``n_sum`` is the number of such values the collective will
    SUM downstream — each chip's contribution clamps to ±(65504 /
    n_sum) so even a coherent worst-case spike across all chips stays
    finite through the f16 accumulation (pre-reduction values merely
    within range are not enough).  Clamping trades silent divergence
    for a bounded, clipping-like bias on the rare overflowing element,
    the same behavior as NCCL-style fp16 rings."""
    wd = jnp.dtype(wire_dtype)
    if wd == jnp.float32:  # replicated-by: config-derived
        return x
    if wd == jnp.float16:
        lim = float(jnp.finfo(jnp.float16).max) / max(1, int(n_sum))
        x = jnp.clip(x, -lim, lim)
    return stochastic_round(x, wire_dtype, key)


def _wire_key(step, tag: int):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(_WIRE_KEY_SALT), step), tag)


def reduce_scatter_grads(plan: BucketPlan, grads, *, wire_dtype,
                         axis_name: str, step) -> List[jnp.ndarray]:
    """Local grad pytree → list of owned f32 slices of the global MEAN
    gradient.  The 1/n pre-scale implements the pmean convention (each
    chip differentiates its local-batch-mean loss); for power-of-two
    meshes the scale is exact, so the f32 wire stays bitwise-equal to
    psum-then-divide."""
    n = plan.n_shard
    # fold the chip index into the downcast key: per-chip grads are
    # SIMILAR in DP, so a shared noise pattern would round the same
    # direction on every chip and the rounding errors would sum
    # coherently (~n·ε) in the psum_scatter instead of canceling
    # (~√n·ε) as independent noise does
    chip = lax.axis_index(axis_name)
    owned = []
    for b, flat in enumerate(flatten_to_buckets(plan, grads)):
        key = jax.random.fold_in(_wire_key(step, b), chip)
        w = wire_cast(flat / n, wire_dtype, key, n_sum=n)
        o = lax.psum_scatter(w, axis_name, scatter_dimension=0, tiled=True)
        owned.append(o.astype(jnp.float32))
    return owned


def all_gather_params(plan: BucketPlan, masters, *, wire_dtype,
                      axis_name: str, step):
    """Owned f32 master slices → replicated f32 param pytree via the
    wire dtype (the FP16 weight re-publish of the reference: replicated
    params carry wire precision; masters stay exact)."""
    gathered = []
    for b, mslice in enumerate(masters):
        w = wire_cast(mslice, wire_dtype,
                      _wire_key(step, plan.num_buckets + b))
        g = lax.all_gather(w, axis_name, axis=0, tiled=True)
        gathered.append(g.astype(jnp.float32))
    return unflatten_from_buckets(plan, gathered)


def clip_slices(owned: List[jnp.ndarray], clip_spec, axis_name: str):
    """Gradient clipping on the owned slices of the REDUCED gradient —
    semantically identical to clipping the full psum'd gradient:
    value-clip is elementwise; the global L2 norm is the psum of
    per-slice square sums (the slices partition the flat vector)."""
    if clip_spec is None:
        return owned
    kind = clip_spec[0]
    if kind == "value":
        _, lo, hi = clip_spec
        return [jnp.clip(o, lo, hi) for o in owned]
    if kind == "norm":
        _, max_norm = clip_spec
        local_sq = sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in owned)
        norm = jnp.sqrt(lax.psum(local_sq, axis_name))
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        return [o * scale for o in owned]
    raise ValueError(f"unknown clip spec {clip_spec!r}")


def sync_and_update(plan: BucketPlan, grads, gs_state: dict, optim_method,
                    lr, step, *, wire_dtype, axis_name: str = "data",
                    clip_spec=None) -> Tuple[Any, dict]:
    """One full AllReduceParameter round on-device (inside shard_map):
    reduce-scatter compressed grads → clip → optimizer update on the
    owned slice → all-gather compressed params.  Returns the new
    replicated param pytree and the new grad_sync state."""
    owned = reduce_scatter_grads(plan, grads, wire_dtype=wire_dtype,
                                 axis_name=axis_name, step=step)
    owned = clip_slices(owned, clip_spec, axis_name)
    masters, inner = optim_method.update(
        owned, gs_state["master"], gs_state["opt"], lr, step)
    params = all_gather_params(plan, masters, wire_dtype=wire_dtype,
                               axis_name=axis_name, step=step)
    return params, {"master": masters, "opt": inner}


def sync_model_state(mstate, axis_name: str):
    """pmean the floating leaves of the post-step model state so the
    replicated out-spec is truthful (BatchNorm running stats become the
    cross-chip average of per-shard statistics — per-partition stats,
    like the reference); integer/bool leaves (counters) advance
    identically on every chip and pass through."""
    return tmap(
        lambda a: lax.pmean(a, axis_name)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        mstate)


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``shard_map`` with replication checking off (grad_sync outputs
    are replicated by construction — psum/pmean/all-gather — which the
    static checker cannot always prove)."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)
