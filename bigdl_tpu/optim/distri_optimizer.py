"""DistriOptimizer — synchronous data-parallel training over a TPU mesh.

Reference: ``DL/optim/DistriOptimizer.scala`` (1,106 LoC) +
``DL/parameters/AllReduceParameter.scala``: each Spark iteration is 2 jobs —
(A) per-executor forward/backward with BlockManager weight fetch and FP16
gradient put, (B) per-node aggregation of its 1/N gradient slice, optimizer
update of its 1/N weight slice, weight re-publish.  That is literally a
reduce-scatter + all-gather with a sharded optimizer update (ZeRO-1).

TPU redesign: ONE jit'd SPMD step-block over a ``jax.sharding.Mesh``,
driven by the shared fused/pipelined loop in ``Optimizer._train_driver``
(K-step ``lax.scan`` fusion + double-buffered device prefetch — the
analog of BigDL 2.0 hiding the per-iteration Spark job dispatch cost).

- The global batch rides the ``data`` mesh axis (the analog of one data
  partition per executor); a staged K-step block is sharded
  ``P(None, "data")`` — step axis replicated, batch axis sharded.
- With ``parameter_sharding=True`` (default, pure DP), gradient sync is
  the EXPLICIT bucketed protocol of ``parallel/grad_sync.py`` — the
  TPU-native ``AllReduceParameter`` + ``FP16CompressedTensor``:
  size-capped grad buckets reduce-scatter over ``data`` in a
  configurable wire dtype (``Config.grad_wire_dtype``: f32|bf16|f16,
  unbiased stochastic-rounded downcast), each chip runs the optimizer
  on its owned f32 master slice (ZeRO-1, ``AllReduceParameter.scala:
  73-76``; arXiv:2004.13336), and updated params all-gather back in the
  wire dtype — all inside ``shard_map`` within the fused K-step jit so
  XLA's latency-hiding scheduler overlaps per-bucket collectives with
  backward compute.  An early revision left gradient aggregation to
  GSPMD's implicit f32 all-reduce on the assumption that ICI makes
  software compression unnecessary — BENCH r05 measured that
  assumption WRONG: ``collective_overhead_fraction = 0.32`` at 8 chips
  (531 ms/step ablated vs 782 ms with collectives), so the wire format
  earns its keep exactly as it did for the reference over Ethernet.
- ``parameter_sharding=False`` (or ``grad_sync=False``) keeps the
  implicit path: params replicated, XLA inserts the f32 gradient
  AllReduce — the baseline the grad_sync numerics tests gate against.
- Straggler gradient-dropping (``DistriOptimizer.scala:398-425``) is
  intentionally absent: SPMD collectives are lock-step; XLA's synchronous
  model replaces it (documented divergence, SURVEY.md §7 stage 4).
- Failure retry-from-checkpoint (``:981-1061``) wraps the driver loop.

Multi-host: each process feeds its local shard of the global batch via
``jax.make_array_from_process_local_data``; ``jax.distributed.initialize``
is the analog of Spark executor registration.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.checkpoint import build_schema
from bigdl_tpu.engine import Engine
from bigdl_tpu.optim.optimizer import (Optimizer, select_step,
                                       step_finite)
from bigdl_tpu.parallel import grad_sync
from bigdl_tpu.resilience.membership import (ClusterMembership,
                                             MembershipChanged)
from bigdl_tpu.resilience.numeric import NonFiniteStepError
from bigdl_tpu.utils import spmdcheck

logger = logging.getLogger("bigdl_tpu.optim")

tmap = jax.tree_util.tree_map


def batch_axis_spec(leaf, mesh: Mesh, axis: str = "data") -> P:
    """Shard dim 0 over the mesh axis when divisible, else replicate —
    used for ZeRO-1-style optimizer-state sharding."""
    n = mesh.shape[axis]
    if leaf.ndim > 0 and leaf.shape[0] % n == 0 and leaf.shape[0] >= n:
        return P(axis)
    return P()


class DistriOptimizer(Optimizer):
    """Data-parallel SPMD trainer.  See module docstring."""

    def __init__(self, model, dataset, criterion, batch_size=None,
                 mesh: Optional[Mesh] = None,
                 parameter_sharding: bool = True,
                 param_specs=None,
                 grad_sync: Optional[bool] = None,
                 grad_wire_dtype: Optional[str] = None,
                 grad_bucket_bytes: Optional[int] = None):
        """``param_specs``: optional pytree of PartitionSpec matching the
        model params — enables tensor parallelism (build with
        ``parallel.tensor_parallel.build_param_specs``).  ``None`` keeps
        params replicated (pure DP).

        ``grad_sync``: force the explicit bucketed gradient-sync path
        (parallel/grad_sync.py) on/off; ``None`` (default) enables it
        whenever ``parameter_sharding`` is on and the run is pure DP
        (no ``param_specs``, non-``data`` mesh axes all size 1).
        ``grad_wire_dtype`` ("f32"|"bf16"|"f16") and
        ``grad_bucket_bytes`` override the ``Config`` defaults."""
        super().__init__(model, dataset, criterion, batch_size)
        self.mesh = mesh or Engine.get_mesh()
        self.parameter_sharding = parameter_sharding
        self.param_specs = param_specs
        self.grad_sync = grad_sync
        self.grad_wire_dtype = grad_wire_dtype
        self.grad_bucket_bytes = grad_bucket_bytes
        self.failure_retry_times = Engine._state.failure_retry_times
        self._param_sh = None
        self._ostate_sh = None
        self._block_sh = None  # P(None, "data"): step axis × batch axis
        self._n_dev = 1
        self._use_grad_sync = False
        self._gs_plan = None
        self._gs_wire = None

    # -------------------------------------------------------- shardings
    def _shardings(self, params, ostate):
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        param_sh = tmap(lambda _: repl, params) if self.param_specs is None \
            else tmap(lambda sp: NamedSharding(mesh, sp), self.param_specs,
                      is_leaf=lambda x: isinstance(x, P))
        if self._use_grad_sync or (self.parameter_sharding
                                   and self.param_specs is None):
            # ZeRO-1: shard optimizer state over the data axis (only when
            # params are replicated — TP already shards the state with
            # them).  grad_sync state (flat master/optimizer buckets,
            # padded to the data-axis size) lands on the same rule: each
            # chip holds exactly the slice it owns.
            ostate_sh = tmap(
                lambda l: NamedSharding(mesh, batch_axis_spec(l, mesh)),
                ostate)
        elif self.param_specs is not None:
            # optimizer-state subtrees (velocity/m/v/...) are tmaps over the
            # params, so a subtree with the params' structure inherits the
            # param shardings leaf-for-leaf; anything else is replicated
            pstruct = jax.tree_util.tree_structure(params)
            ostate_sh = {}
            for key, sub in ostate.items():
                if jax.tree_util.tree_structure(sub) == pstruct:
                    ostate_sh[key] = param_sh
                else:
                    ostate_sh[key] = tmap(lambda _: repl, sub)
        else:
            ostate_sh = tmap(lambda _: repl, ostate)
        return repl, param_sh, ostate_sh

    # ---------------------------------------------- explicit grad sync
    def _resolve_grad_sync(self, mesh: Mesh, params) -> None:
        """Decide whether this run takes the explicit grad_sync path and
        build its static bucket plan.  Pure-DP only: tensor parallelism
        shards the params themselves, so the flat-bucket ZeRO-1 protocol
        does not apply (those runs keep the constraint-driven path)."""
        pure_dp = (self.param_specs is None and "data" in mesh.axis_names
                   and all(mesh.shape[a] == 1 for a in mesh.axis_names
                           if a != "data"))
        if self.grad_sync is None:
            use = self.parameter_sharding and pure_dp
        else:
            use = bool(self.grad_sync)
            if use and not pure_dp:
                raise ValueError(
                    "grad_sync=True requires a pure data-parallel run "
                    "(no param_specs, non-data mesh axes of size 1); "
                    f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
        self._use_grad_sync = use
        if not use:
            return
        if self.grad_clip is not None and self.grad_clip_spec is None:
            raise ValueError(
                "grad_sync clips owned slices of the reduced gradient and "
                "needs a structured clip spec — use "
                "set_gradient_clipping_by_value/_by_l2_norm (or "
                "grad_sync=False for a custom grad_clip callable)")
        # constructor args win; otherwise the Config fields
        from bigdl_tpu.utils.config import get_config
        cfg = get_config()
        wire = self.grad_wire_dtype if self.grad_wire_dtype is not None \
            else cfg.grad_wire_dtype
        bucket = self.grad_bucket_bytes \
            if self.grad_bucket_bytes is not None \
            else cfg.grad_bucket_bytes
        self._gs_wire = grad_sync.resolve_wire_dtype(wire)
        self._gs_plan = grad_sync.build_plan(
            params, mesh.shape["data"], int(bucket))

    def _check_resumed_opt_state(self, ostate) -> None:
        """Fail LOUDLY when a retry/resume checkpoint's opt_state was
        written by the other sync path — the formats differ (grad_sync:
        ``{"master": [flat buckets], "opt": ...}`` vs per-leaf pytree)
        and letting the mismatch reach jit tracing produces an opaque
        KeyError/structure error instead of this message."""
        is_gs = (isinstance(ostate, dict) and set(ostate) ==
                 {"master", "opt"} and isinstance(ostate.get("master"),
                                                  list))
        if self._use_grad_sync and not is_gs:
            raise ValueError(
                "resumed opt_state is not grad_sync-format (expected "
                "{'master': [...], 'opt': ...}) — the checkpoint was "
                "written by a non-grad_sync run; resume with the "
                "matching setting (grad_sync=False / "
                "parameter_sharding=False) or clear the checkpoint dir")
        if not self._use_grad_sync and is_gs:
            raise ValueError(
                "resumed opt_state is grad_sync-format but this run has "
                "grad_sync disabled — re-enable it or clear the "
                "checkpoint dir")
        if is_gs:
            want = [(s,) for s in self._gs_plan.bucket_sizes]
            got = [tuple(m.shape) for m in ostate["master"]]
            if want != got:
                raise ValueError(
                    f"resumed grad_sync masters {got} do not match this "
                    f"run's bucket plan {want} — mesh size or "
                    f"grad_bucket_bytes changed since the checkpoint "
                    f"was written")

    # ------------------------------------------------- elastic membership
    def set_elastic(self,
                    membership: Optional[ClusterMembership] = None
                    ) -> "DistriOptimizer":
        """Arm elastic training: membership epochs over THIS mesh's
        device pool.  A ``resize``/``host_loss``/``device_loss`` fault
        clause (or an explicit ``request_resize`` on the returned
        membership) opens a new epoch; the driver detects it at the
        replay boundary, snapshots, and ``optimize()`` resumes on the
        new roster with the ZeRO-1 state re-sharded.  Built ONCE per
        optimizer — epochs stay monotonic across every shrink/regrow
        cycle of one run (4 → 2 → 4 ends at epoch 3, not 1)."""
        if self._membership is None:
            self._membership = membership if membership is not None \
                else ClusterMembership(
                    tuple(self.mesh.devices.flat),
                    registry=self.metrics.registry,
                    recorder=getattr(self, "_flight", None))
        return self

    def _arm_membership_from_plan(self, faults) -> None:
        if faults is None or not faults.has_membership_kinds():
            return
        self.set_elastic()

    # replay-boundary: runs before any block is staged on this epoch
    def _adopt_membership_roster(self) -> None:
        """An epoch opened BETWEEN runs (operator ``request_resize``
        before ``optimize()``): nothing is in flight, so adopt the
        roster up front — no snapshot restore, no steps lost.  Must run
        BEFORE any placement/sharding derives from ``self.mesh``;
        without it the run would dispatch on the stale mesh while the
        membership ledger says otherwise."""
        m = self._membership
        if m is None:
            return
        cur = m.current()
        # replicated-by: membership-epoch-ledger
        if tuple(cur.devices) == tuple(self.mesh.devices.flat):
            return
        # spmdcheck: roster adoption re-keys every later collective (new
        # mesh) — all processes must adopt the same epoch here
        spmdcheck.note("membership_adopt", axis=f"epoch{cur.epoch}")
        self.mesh = Mesh(np.asarray(cur.devices), ("data",))
        if self.model._params is not None:
            # params may still be committed to the old roster's devices
            # — pull them to host so this run's dispatch commits them
            # to the adopted mesh (the restore path gets host arrays
            # from the snapshot for free)
            self.model._params = jax.device_get(self.model._params)
            self.model._state = jax.device_get(self.model._state)
        logger.warning(
            "membership epoch %d (%s): adopting world=%d roster "
            "at run start", cur.epoch, cur.reason, cur.world)
        self._flight_event("resize_adopt", epoch=cur.epoch,
                           world=cur.world, reason=cur.reason)

    # replay-boundary: the driver replayed/abandoned the in-flight block
    # before raising MembershipChanged — restore lands on a block edge
    def _resume_after_resize(self, e: MembershipChanged) -> None:
        """Rebuild the mesh on the new epoch's roster and restore the
        latest valid snapshot so the next ``_optimize_impl`` resumes on
        it (the grad_sync state is re-sharded there, where the new
        bucket plan exists).  Called from ``optimize()``'s
        :class:`MembershipChanged` handler — a resize is a measured
        event, not a failure, so it never burns the retry budget."""
        ep = e.epoch
        self.mesh = Mesh(np.asarray(ep.devices), ("data",))
        logger.warning(
            "membership epoch %d (%s, graceful=%s): resuming on "
            "world=%d", ep.epoch, ep.reason, ep.graceful, ep.world)
        mgr = self._checkpoint_manager()
        mgr.wait()
        ckpt = mgr.latest_valid()
        if ckpt is None:
            raise RuntimeError(
                f"membership epoch {ep.epoch} ({ep.reason}) but no "
                f"valid snapshot under {self.checkpoint_path} to "
                f"resume from — elastic training needs one committed "
                f"snapshot before an abrupt device loss") from e
        mgr.restore_into(self, ckpt, verified=True)
        lost = max(0, e.detected_neval - int(self.state["neval"]))
        self.metrics.registry.counter(
            "resilience/steps_lost_to_resize").inc(lost)
        self._flight_event("resize_restore", epoch=ep.epoch,
                           world=ep.world, reason=ep.reason,
                           steps_lost=lost,
                           iteration=int(self.state["neval"]))
        # downtime clock keeps running until the resumed driver stages
        # its first block (observed there as resilience/resize_downtime_s)
        self._resize_t0 = e.t0

    def _maybe_reshard_resumed(self, ostate):
        """Elastic resume of a grad_sync state written at a DIFFERENT
        world size: strip the old per-shard padding, re-pad each flat
        bucket to this run's plan (``grad_sync.reshard_state`` —
        padding is zeros and elementwise optimizers map zeros to zeros,
        so the re-bucketing is information-preserving).  Non-elastic
        runs fall through to ``_check_resumed_opt_state``'s hard
        refusal unchanged."""
        if self._membership is None or not self._use_grad_sync:
            return ostate
        is_gs = (isinstance(ostate, dict) and set(ostate) ==
                 {"master", "opt"} and isinstance(ostate.get("master"),
                                                  list))
        if not is_gs:
            return ostate
        want = [(s,) for s in self._gs_plan.bucket_sizes]
        got = [tuple(np.shape(m)) for m in ostate["master"]]
        # plan shapes derive from config + model; the restored state is
        # the same snapshot on every host
        # replicated-by: snapshot-schema
        if want == got:
            return ostate
        logger.info(
            "elastic resume: re-sharding grad_sync state %s -> %s "
            "(n_shard=%d)", got, want, self._gs_plan.n_shard)
        return grad_sync.reshard_state(self._gs_plan, ostate)

    def _build_block_fn(self, grad_fn, k: int):
        """grad_sync runs: ONE donated jit whose body is a ``shard_map``
        over the mesh — per-chip forward/backward on the local batch
        shard, then the explicit reduce-scatter → owned-slice update →
        all-gather of ``parallel/grad_sync.py`` (K-step ``lax.scan``
        INSIDE the shard_map, so per-bucket collectives of step j can
        overlap compute of step j+1 under XLA's latency-hiding
        scheduler).  Non-grad_sync runs keep the base GSPMD block."""
        if not self._use_grad_sync:
            return super()._build_block_fn(grad_fn, k)
        from functools import partial

        mesh, axis = self.mesh, "data"
        n = mesh.shape[axis]
        plan, wire = self._gs_plan, self._gs_wire
        optim = self.optim_method
        clip_spec = self.grad_clip_spec if self.grad_clip is not None \
            else None

        guard = self._resolved_numeric_guard()

        def one_step(params, mstate, ostate, x, y, lr, step, rng):
            (loss, new_mstate), grads = grad_fn(params, mstate, x, y, rng)
            if guard != "off":
                # mesh-global finite verdict: every chip must agree so
                # the jnp.where gate below selects identically on every
                # owned ZeRO-1 slice (pmin of the local flags — one
                # poisoned chip vetoes the whole step)
                finite = jax.lax.pmin(
                    step_finite(loss, grads).astype(jnp.int32),
                    axis).astype(bool)
            new_params, new_ostate = grad_sync.sync_and_update(
                plan, grads, ostate, optim, lr, step,
                wire_dtype=wire, axis_name=axis, clip_spec=clip_spec)
            synced_mstate = grad_sync.sync_model_state(new_mstate, axis)
            loss_out = jax.lax.pmean(loss, axis)
            if guard == "off":
                return new_params, synced_mstate, new_ostate, loss_out
            if guard == "skip":
                return (select_step(finite, new_params, params),
                        select_step(finite, synced_mstate, mstate),
                        select_step(finite, new_ostate, ostate),
                        (loss_out, finite))
            return new_params, synced_mstate, new_ostate, \
                (loss_out, finite)

        body = self._block_body(one_step, k)

        def ostate_spec(l):
            # flat bucket leaves (masters + mirrored optimizer state)
            # shard over `data` — the SAME ownership predicate the host
            # placement uses (batch_axis_spec), so in_specs can never
            # disagree with where _optimize_impl put the state
            return batch_axis_spec(l, mesh, axis)

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def block_fn(params, mstate, ostate, xs, ys, lrs, steps, rngs):
            for leaf in jax.tree_util.tree_leaves(xs):
                if leaf.shape[1] % n:
                    raise ValueError(
                        f"grad_sync needs the batch divisible by the "
                        f"data axis: got {leaf.shape[1]} rows over "
                        f"{n} chips — pad/drop the remainder or pass "
                        f"grad_sync=False")
            os_spec = tmap(ostate_spec, ostate)
            in_specs = (tmap(lambda _: P(), params),
                        tmap(lambda _: P(), mstate),
                        os_spec,
                        tmap(lambda _: P(None, axis), xs),
                        None if ys is None
                        else tmap(lambda _: P(None, axis), ys),
                        P(), P(), P())
            out_specs = (tmap(lambda _: P(), params),
                         tmap(lambda _: P(), mstate),
                         os_spec, P())
            fn = grad_sync.shard_map_unchecked(body, mesh, in_specs,
                                               out_specs)
            return fn(params, mstate, ostate, xs, ys, lrs, steps, rngs)

        return block_fn

    def _make_global(self, arr: np.ndarray, sharding: NamedSharding):
        """Per-host local shard → global device array (multi-host safe)."""
        # spmdcheck: assembling a global array is a rendezvous — noted
        # even on the single-process path so emulated schedules match
        # what a real pod would run
        spmdcheck.note("make_global", payload=arr)
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_process_local_data(sharding, arr)

    # ----------------------------------------------- train-driver hooks
    def _place_train_block(self, xs, ys):
        """Staged (K, local_batch, ...) host trees → global arrays with
        the step axis replicated and the batch axis sharded over `data`
        (the per-microbatch analog of one data partition per executor).
        The ``device_put`` underneath is asynchronous — the driver
        stages block i+1 while block i computes, so this is where the
        double-buffered host→HBM transfer actually happens."""
        place = lambda a: self._make_global(np.asarray(a), self._block_sh)
        xs = tmap(place, xs)
        ys = None if ys is None else tmap(place, ys)
        return xs, ys

    def _records_scale(self) -> int:
        # batch.size() is the PER-HOST local batch; under multi-host the
        # assembled global array is process_count× larger, and epoch
        # accounting compares against the GLOBAL dataset.size()
        return jax.process_count()

    def _constrain_step_outputs(self, params, ostate):
        # pin output layouts so the pattern stays reduce-scatter+gather
        # (ZeRO-1) / TP-sharded across every step of the scanned block
        params = jax.lax.with_sharding_constraint(params, self._param_sh)
        ostate = jax.lax.with_sharding_constraint(ostate, self._ostate_sh)
        return params, ostate

    def _log_train_iteration(self, lr: float) -> None:
        s = self.state
        logger.info(
            "epoch %d iter %d loss %.4f lr %.5g throughput %.1f rec/s "
            "(%.1f rec/s/dev)",
            s["epoch"], s["neval"], s["loss"], lr, s["throughput"],
            s["throughput"] / self._n_dev)

    def _log_parameter_histograms(self, params) -> None:
        # trigger-gated per-parameter histograms (reference
        # DistriOptimizer.scala:541-573 "Parameters" summary)
        ptrig = getattr(self.train_summary, "trigger_for",
                        lambda _n: None)("Parameters")
        if ptrig is not None and ptrig(self.state):
            flat = jax.tree_util.tree_flatten_with_path(params)[0]
            for path, leaf in flat:
                tag = "Parameters/" + "/".join(
                    str(getattr(k, "key", k)) for k in path)
                self.train_summary.add_histogram(
                    tag, np.asarray(leaf), self.state["neval"])

    # ------------------------------------------- multi-host-safe val/ckpt
    # Eval placement hooks: batches go through the same ``_make_global``
    # path as training inputs, so validation is correct on real multi-host
    # jobs (the base hooks feed host-local arrays into a jit against
    # global params — single-process only).
    #
    # Multi-host contract: every process must see the SAME number of
    # validation batches and identical batch shapes (the framework's own
    # per-host dataset sharding guarantees this); the hooks issue one
    # collective per batch, so unequal counts would deadlock.
    def _place_eval_input(self, x):
        n_data = self.mesh.shape["data"]
        data_sh = NamedSharding(self.mesh, P("data"))
        repl = NamedSharding(self.mesh, P())

        def place(a):
            a = np.asarray(a)
            # the dataset layer shards per host from the same global
            # source: batch shapes (and the ragged tail, if any) are
            # identical on every process, so the fallback choice —
            # and the collective in _make_global — stays uniform
            # replicated-by: global-batch-layout
            if a.shape[0] % n_data == 0:
                return self._make_global(a, data_sh)
            # ragged last eval batch: single-process can fall back to a
            # replicated (unsharded but correct) forward; multi-host has
            # no safe fallback — per-process rows differ, so a
            # "replicated" global array would be undefined
            if jax.process_count() > 1:
                raise ValueError(
                    f"multi-host validation batch of {a.shape[0]} rows is "
                    f"not divisible by the data axis ({n_data}); use a "
                    "divisible validation batch size (drop_remainder or "
                    "pad)")
            return jax.device_put(a, repl)

        return tmap(place, x)

    def _place_eval_target(self, t):
        return tmap(lambda a: self._host_global(np.asarray(a)), t)

    def _gather_eval_output(self, out):
        return self._host_global(out)

    def _host_global(self, arr):
        """Globally-sharded device array → host array every process sees
        fully (process_allgather under multi-host)."""
        # spmdcheck: noted before the single-process early return so the
        # emulated schedule records the allgather a real pod would issue
        spmdcheck.note("allgather", payload=arr)
        if jax.process_count() == 1:
            return arr
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(arr, tiled=True)

    def _do_checkpoint(self, params, mstate, ostate,
                       sync: bool = False) -> None:
        if jax.process_count() > 1:
            # sharded leaves are not fully addressable on one process:
            # allgather to host, then only process 0 writes
            params = tmap(self._host_global, params)
            mstate = tmap(self._host_global, mstate)
            ostate = tmap(self._host_global, ostate)
            if jax.process_index() != 0:
                # record the step on EVERY process: the preemption
                # branch's already-saved dedup reads last_saved_step,
                # and a process-0-only update would make that predicate
                # diverge — non-zero hosts would enter the allgather
                # above while process 0 skips it (collective deadlock)
                # replicates: checkpoint-step-mirror
                self._checkpoint_manager().last_saved_step = \
                    int(self.state["neval"])
                return
        super()._do_checkpoint(params, mstate, ostate, sync=sync)

    def _checkpoint_schema(self, params) -> dict:
        if not self._use_grad_sync:
            return super()._checkpoint_schema(params)
        return build_schema(
            params, grad_sync=True,
            bucket_sizes=self._gs_plan.bucket_sizes,
            wire_dtype=jnp.dtype(self._gs_wire).name,
            n_shard=self._gs_plan.n_shard,
            optim_method=type(self.optim_method).__name__,
            bucket_content=grad_sync.bucket_content_sizes(self._gs_plan))

    # ------------------------------------------------------------- train
    # replay-boundary: restores happen only between _optimize_impl runs,
    # after the failed run's blocks are torn down
    def optimize(self):
        attempts = 0
        while True:
            try:
                return self._optimize_impl()
            except MembershipChanged as e:
                # elastic resize: the driver already replayed/abandoned
                # the in-flight block and secured a boundary snapshot —
                # rebuild the mesh on the new roster, restore, and go
                # again.  A measured event, not a failure: the retry
                # budget is untouched.
                self._resume_after_resize(e)
            except NonFiniteStepError as e:
                # numeric_guard: "abort" must surface at the exact
                # iteration — the one failure class the reference-style
                # retry loop below must NOT swallow; "rollback" runs
                # the shared restore-latest-valid recovery.  The budget
                # is read LIVE from config (like LocalOptimizer and the
                # dispatch-retry loop), not from the Engine-init
                # snapshot the legacy loop below still uses.
                attempts += 1
                from bigdl_tpu.utils.config import get_config
                self._rollback_nonfinite(
                    e, attempts, get_config().failure_retry_times)
            except Exception:
                # reference retry-from-checkpoint loop
                # (DistriOptimizer.scala:981-1061), now on the manager:
                # discovery returns the latest VALID snapshot (a torn/
                # corrupt file from the crash window is skipped, never
                # loaded) and restore_into brings back the FULL state —
                # params, model state, optimizer state (Adam moments /
                # grad_sync masters; schema-validated in the next
                # _optimize_impl), driver counters, RNG seed and the
                # dataset shuffle position, so the retried run replays
                # the interrupted one exactly
                attempts += 1
                if attempts > self.failure_retry_times \
                        or not self.checkpoint_path:
                    raise
                mgr = self._checkpoint_manager()
                ckpt = mgr.latest_valid()
                if ckpt is None:
                    raise
                logger.exception(
                    "training failed; retry %d/%d from %s",
                    attempts, self.failure_retry_times, ckpt)
                mgr.restore_into(self, ckpt, verified=True)

    def _optimize_impl(self):
        self._adopt_membership_roster()
        mesh = self.mesh
        self._n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        rng = jax.random.PRNGKey(self.seed)
        rng, init_rng = jax.random.split(rng)
        if self.model._params is not None:
            # copy: the block fn donates its inputs; without this the
            # caller-owned model arrays would be deleted by donation
            # (device_put below is a no-op for already-placed arrays)
            params = jax.tree_util.tree_map(jnp.array, self.model._params)
            mstate = jax.tree_util.tree_map(jnp.array, self.model._state)
        else:
            params, mstate = self.model.init(init_rng)
        self._resolve_grad_sync(mesh, params)
        self._validate_resume_schema(params)
        if self._resume_opt_state is not None:
            ostate = self._resume_opt_state
            self._resume_opt_state = None
            ostate = self._maybe_reshard_resumed(ostate)
            self._check_resumed_opt_state(ostate)
        elif self._use_grad_sync:
            ostate = grad_sync.init_state(self._gs_plan, params,
                                          self.optim_method)
        else:
            ostate = self.optim_method.init_state(params)
        repl, param_sh, ostate_sh = self._shardings(params, ostate)
        self._param_sh, self._ostate_sh = param_sh, ostate_sh
        self._block_sh = NamedSharding(mesh, P(None, "data"))

        # place initial values
        params = tmap(lambda x, s: jax.device_put(x, s), params, param_sh)
        ostate = tmap(lambda x, s: jax.device_put(x, s), ostate, ostate_sh)
        mstate = tmap(lambda x: jax.device_put(x, repl), mstate)

        grad_fn = self._loss_and_grad_fn()
        logger.info(
            "DistriOptimizer: %d samples/epoch, mesh=%s, grad_sync=%s%s",
            self.dataset.size(),
            dict(zip(mesh.axis_names, mesh.devices.shape)),
            self._use_grad_sync,
            f" (wire={jnp.dtype(self._gs_wire).name}, "
            f"buckets={self._gs_plan.num_buckets})"
            if self._use_grad_sync else
            f" (zero1={self.parameter_sharding})")

        params, mstate, ostate = self._train_driver(params, mstate, ostate,
                                                    grad_fn, rng)
        # as LocalOptimizer: a model's ``state_warnings``, after the run
        for said in getattr(self.model, "state_warnings",
                            lambda state: [])(mstate):
            logger.warning("%s", said)

        self.model._params = params
        self.model._state = mstate
        self._final_opt_state = ostate
        return self.model
