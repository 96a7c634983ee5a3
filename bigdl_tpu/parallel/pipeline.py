"""Pipeline parallelism over the mesh's ``pipe`` axis (GPipe-style).

The reference has NO pipeline parallelism (SURVEY §2.9 — Spark-era BigDL
is pure data-parallel); this is a beyond-reference capability the TPU
build adds, filling the ``pipe`` mesh axis declared in ``parallel/mesh.py``.

TPU-idiomatic design (the scaling-book collective-permute recipe, not a
host-driven scheduler):

- **Stages are stacked**: a pipeline of S identical-structure stages keeps
  its parameters as one pytree with a leading ``(S, ...)`` axis, sharded
  over ``pipe`` — each device holds exactly its stage's slice (the PP
  memory win).
- **The schedule is one ``lax.scan`` inside ``shard_map``**: T = M + S - 1
  ticks for M microbatches.  Every tick each rank applies its stage to its
  current activation and the result is ``ppermute``d to rank+1 while rank
  0 ingests the next microbatch — all ranks stay busy after the S-1-tick
  fill.  Bubble fraction = (S-1)/T, amortized by M like GPipe.
- **Backward is just ``jax.grad``** through the scan + ppermute (both
  differentiable); no hand-written 1F1B machinery.

Heterogeneous ``Sequential`` models: :func:`partition_sequential` splits
layers into S balanced stage lists; those are only stackable when the
stages share a pytree structure (e.g. repeated blocks).  For arbitrary
stage structures use :class:`MicrobatchedSequential`, which reproduces
GPipe's exact math (microbatched loss == full-batch loss) without the
spatial placement — correctness path for the dryrun and small meshes.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import Module, Sequential


# ------------------------------------------------------- stage partitioning
def partition_sequential(model: Sequential, num_stages: int
                         ) -> List[Sequential]:
    """Split a Sequential's children into ``num_stages`` balanced stages
    (by layer count).  Mirrors GPipe's per-device partitioning."""
    mods = list(model.modules)
    if num_stages <= 0 or num_stages > len(mods):
        raise ValueError(f"cannot split {len(mods)} layers into "
                         f"{num_stages} stages")
    sizes = [len(mods) // num_stages] * num_stages
    for i in range(len(mods) % num_stages):
        sizes[i] += 1
    stages, ix = [], 0
    for s in sizes:
        stages.append(Sequential(*mods[ix:ix + s]))
        ix += s
    return stages


# ------------------------------------------------------------ stacked GPipe
class GPipe(Module):
    """SPMD pipeline of S identical-structure stages.

    ``stage``: a Module whose ``apply(params, {}, x)`` maps activations to
    activations with the same pytree structure of params at every stage
    (e.g. one transformer block, one MLP block).  ``init`` stacks S
    independent initializations into leading-axis-S arrays; under a mesh
    the caller shards that axis over ``pipe``.

    ``apply`` expects input already split into microbatches:
    ``(M, mb, ...)``; it returns ``(M, mb, ...)`` outputs.
    """

    def __init__(self, stage: Module, num_stages: int,
                 mesh: Optional[Mesh] = None, axis: str = "pipe",
                 name: Optional[str] = None):
        super().__init__(name)
        self.stage = stage
        self.num_stages = num_stages
        self.mesh = mesh
        self.axis = axis
        # eager state-template capture: the pipelined schedule needs the
        # stage's static state STRUCTURE even when the caller threads no
        # state; computing it at construction keeps apply() free of
        # host-side memo writes inside a traced scope
        _, self._state_template = stage.init(jax.random.PRNGKey(0))

    def init(self, rng):
        ks = jax.random.split(rng, self.num_stages)
        inits = [self.stage.init(k) for k in ks]
        params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[p for p, _ in inits])
        # per-stage STATE is stacked the same way (leading S axis) and
        # threaded through the pipelined schedule — BN running stats work
        state = {}
        if jax.tree_util.tree_leaves(inits[0][1]):
            state = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[s for _, s in inits])
        self._state_template = inits[0][1]
        return params, state

    def stage_sharding(self) -> NamedSharding:
        """Sharding that gives each pipe rank its stage slice."""
        assert self.mesh is not None
        return NamedSharding(self.mesh, P(self.axis))

    def _template(self):
        return self._state_template

    # pure single-device reference (for parity tests): sequential stages
    def apply_reference(self, params, state, x, *, training=False):
        M = x.shape[0]
        has_state = bool(jax.tree_util.tree_leaves(state))
        out = x.reshape((-1,) + x.shape[2:])
        new_states = []
        for s in range(self.num_stages):
            p_s = jax.tree_util.tree_map(lambda a, s=s: a[s], params)
            st_s = jax.tree_util.tree_map(lambda a, s=s: a[s], state) \
                if has_state else self._template()
            out, ns = self.stage.apply(p_s, st_s, out, training=training)
            new_states.append(ns)
        if has_state:
            state = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                           *new_states)
        return out.reshape((M,) + x.shape[1:]), state

    def apply(self, params, state, input, *, training=False, rng=None):
        """Microbatched pipelined forward under shard_map.

        input: (M, mb, ...) microbatches with M divisible by S; the
        microbatch axis is SHARDED over ``pipe`` (each rank holds M/S
        microbatches — no replicated O(M·mb) feed), and outputs come
        back the same way.  Requires a mesh whose ``self.axis`` size ==
        num_stages."""
        if self.mesh is None:
            return self.apply_reference(params, state, input,
                                        training=training)
        S, axis = self.num_stages, self.axis
        M = input.shape[0]
        if M % S:
            raise ValueError(f"microbatch count {M} must divide by "
                             f"pipeline stages {S}")
        chunk = M // S
        stage_apply = self.stage.apply
        has_state = bool(jax.tree_util.tree_leaves(state))
        template = self._template()

        def pipeline_rank(p_stage, st_stage, xs_local):
            # p_stage/st_stage: this rank's stage slice (leading axis 1);
            # xs_local: this rank's (M/S, mb, ...) chunk of the feed
            p = jax.tree_util.tree_map(lambda a: a[0], p_stage)
            st = jax.tree_util.tree_map(lambda a: a[0], st_stage) \
                if has_state else template
            rank = lax.axis_index(axis)
            T = M + S - 1
            buf = jnp.zeros_like(xs_local[0])     # current activation
            outs = jnp.zeros_like(xs_local)       # this rank's output chunk

            def tick(carry, t):
                buf, outs, st = carry
                # the owner of microbatch t contributes it; psum of the
                # one-hot contribution = distributed queue pop for rank 0
                owner = t // chunk
                local_ix = jnp.clip(t - rank * chunk, 0, chunk - 1)
                mine = jnp.where(rank == owner, xs_local[local_ix],
                                 jnp.zeros_like(xs_local[local_ix]))
                feed = lax.psum(mine, axis)
                x_in = jnp.where(rank == 0, feed, buf)
                y, st_new = stage_apply(p, st, x_in, training=training)
                # this rank's stage sees VALID data only for ticks
                # rank <= t < rank+M: freeze state updates on bubbles
                # (fill/drain garbage must not pollute BN stats)
                valid = (t >= rank) & (t < rank + M)
                if has_state:
                    st = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(valid, new, old),
                        st_new, st)
                # send to next rank; ring wraps, rank 0's incoming unused
                y_next = lax.ppermute(
                    y, axis, [(i, (i + 1) % S) for i in range(S)])
                # last rank finished microbatch t-(S-1) at tick t: route
                # it to the OWNING rank's output chunk (psum one-hot)
                done_ix = t - (S - 1)
                done = jnp.where((rank == S - 1) & (done_ix >= 0), y, 0.0)
                done = lax.psum(done, axis)
                out_owner = jnp.maximum(done_ix, 0) // chunk
                out_local = jnp.clip(done_ix - rank * chunk, 0, chunk - 1)
                write = (done_ix >= 0) & (out_owner == rank)
                outs = lax.cond(
                    write,
                    lambda o: o.at[out_local].set(done),
                    lambda o: o, outs)
                return (y_next, outs, st), None

            (buf, outs, st), _ = lax.scan(tick, (buf, outs, st),
                                          jnp.arange(T))
            st_out = jax.tree_util.tree_map(lambda a: a[None], st) \
                if has_state else {}
            return outs, st_out

        fn = shard_map(
            pipeline_rank, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(self.axis), params),
                      jax.tree_util.tree_map(lambda _: P(self.axis), state),
                      P(self.axis)),
            out_specs=(P(self.axis),
                       jax.tree_util.tree_map(lambda _: P(self.axis),
                                              state)),
            check_vma=False)
        outs, new_state = fn(params, state, input)
        return outs, new_state


class MicrobatchedSequential(Module):
    """GPipe math without spatial placement: run each microbatch through
    heterogeneous stages sequentially and concatenate.  For stateless
    layers the recombined output is bit-identical to the unpipelined
    model; stateful layers (BatchNorm) see the microbatches sequentially —
    state is threaded microbatch-to-microbatch, so running statistics
    advance once per microbatch (M small-batch updates, the standard
    microbatching semantics, not one full-batch update)."""

    def __init__(self, stages: Sequence[Module],
                 num_microbatches: int, name: Optional[str] = None):
        super().__init__(name)
        self.stages = list(stages)
        self.num_microbatches = num_microbatches

    def spec_children(self):
        return {str(i): m for i, m in enumerate(self.stages)}

    def init(self, rng):
        params, state = {}, {}
        for i, m in enumerate(self.stages):
            rng, sub = jax.random.split(rng)
            p, s = m.init(sub)
            params[str(i)] = p
            state[str(i)] = s
        return params, state

    def apply(self, params, state, input, *, training=False, rng=None):
        N = input.shape[0]
        M = self.num_microbatches
        if N % M:
            raise ValueError(f"batch {N} not divisible into {M} microbatches")
        mbs = input.reshape((M, N // M) + input.shape[1:])

        def run_one(x, cur_state):
            new_state = {}
            for i, m in enumerate(self.stages):
                x, s = m.apply(params[str(i)], cur_state[str(i)], x,
                               training=training)
                new_state[str(i)] = s
            return x, new_state

        outs = []
        cur = state  # thread state through microbatches (BN running stats
        # advance per microbatch instead of keeping only the last update)
        for i in range(M):
            o, cur = run_one(mbs[i], cur)
            outs.append(o)
        outs = jnp.stack(outs)
        return outs.reshape((N,) + outs.shape[2:]), cur
