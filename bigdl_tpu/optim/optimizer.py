"""Optimizer — the training front door.

Reference: ``DL/optim/Optimizer.scala:47`` builder API (``setValidation``,
``setCheckpoint:198``, ``overWriteCheckpoint:233``, ``setOptimMethod:366``,
``setEndWhen:389``, gradient clipping ``:423+``) whose factory dispatches
``LocalOptimizer`` (single JVM) vs ``DistriOptimizer`` (Spark).

Here: :class:`Optimizer` holds the builder surface + the ONE driver loop
both trainers share; :class:`LocalOptimizer` jit-compiles the train step
for the local device (1 TPU chip); ``DistriOptimizer`` (bigdl_tpu.optim.
distri_optimizer) shard_maps it over the mesh via the placement /
sharding-constraint hooks.  The factory ``Optimizer.create`` mirrors the
reference's dispatch.

Driver-loop design (the analog of hiding the reference's per-iteration
2-Spark-job orchestration cost, ``DistriOptimizer.scala``'s step):

- **K-step dispatch fusion**: ``steps_per_dispatch = K`` stacks K
  microbatches and runs the (loss, grad, update) step under ``lax.scan``
  inside ONE jit with donated params/mstate/ostate — one host dispatch
  per K iterations instead of per iteration.  The per-step loss vector
  comes back so triggers/summaries still observe every iteration.
- **Exact trigger/epoch semantics**: blocks are planned with
  ``trigger.probe_fire_step`` so a validation/checkpoint/end iteration
  is always a block's LAST step, and epoch boundaries flush partial
  blocks (the stager's records budget) — iteration numbers, shuffle
  cadence, and mid-epoch resume behave identically for every K.
- **Pipelined host work**: the next block is staged (host-stacked and
  asynchronously ``device_put``) right after a dispatch, so the
  host→HBM transfer of block i+1 overlaps the compute of block i; the
  blocking loss fetch runs ONE BLOCK BEHIND the dispatch, so the device
  queue is never drained by a ``float(loss)`` — not even at K=1.

Documented divergence: triggers keyed on ``loss``/``score`` (min_loss,
max_score) are probed with their last known values, so under pipelining
they stop/validate at the correct *iteration number* but the device may
already have run up to one extra block (the final params then include
those extra steps).  Iteration- and epoch-count triggers are exact.

Gradient clipping maps the reference's ``ConstantClippingProcessor`` /
``L2NormClippingProcessor`` (``parameters/ParameterOperations.scala:71,89``)
to pure pytree ops inside the jit'd step — the cross-partition sqsum
aggregation becomes a global norm over the (already full) gradient pytree,
and under data parallelism the psum'd gradient is identical on every
device, so clipping semantics match the reference exactly.
"""

from __future__ import annotations

import logging
import sys
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.prefetch import DeviceBlockStager
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.engine import Engine
from bigdl_tpu.nn.criterion import Criterion
from bigdl_tpu.nn.module import Module
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger, max_epoch, probe_fire_step
from bigdl_tpu.optim.validation import ValidationMethod, ValidationResult
from bigdl_tpu.checkpoint import (CheckpointManager, PreemptionHandler,
                                  build_schema, validate_schema)
from bigdl_tpu.resilience.faults import FaultInjector, InjectedFault
from bigdl_tpu.resilience.membership import (ClusterMembership,
                                             MembershipChanged)
from bigdl_tpu.resilience.numeric import (NonFiniteStepError,
                                          validate_policy)
from bigdl_tpu.telemetry import DriverTelemetry, NULL_SPAN, jit_cache_size
from bigdl_tpu.utils import spmdcheck
from bigdl_tpu.utils.metrics import Metrics

logger = logging.getLogger("bigdl_tpu.optim")

tmap = jax.tree_util.tree_map


def device_tree(x):
    """Move a (possibly nested tuple/list/dict) batch onto device —
    MiniBatch inputs may be pytrees (multi-input models), so a blind
    ``jnp.asarray`` would mis-stack them into one array."""
    return tmap(jnp.asarray, x)


def clip_by_value(grads, min_v: float, max_v: float):
    """(reference ConstantClippingProcessor)"""
    return tmap(lambda g: jnp.clip(g, min_v, max_v), grads)


def global_norm(grads) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))


def clip_by_global_norm(grads, max_norm: float):
    """(reference L2NormClippingProcessor — global norm across all slices)"""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return tmap(lambda g: g * scale, grads)


def step_finite(loss, grads):
    """Scalar bool: this step's loss AND every (inexact) gradient leaf
    are finite.  Computed INSIDE the jit'd step so the flag rides the
    one-block-behind loss fetch — the numeric guard never adds a host
    sync (graftlint catalog: "the numeric guard rides the replay
    boundary")."""
    finite = jnp.isfinite(loss)
    for g in jax.tree_util.tree_leaves(grads):
        if jnp.issubdtype(g.dtype, jnp.inexact):
            finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
    return finite


def select_step(finite, new, old):
    """``jnp.where``-select a whole pytree: the updated binding where
    the step was finite, the pre-step binding otherwise (the dynamic
    loss-scaling skip idiom — a skipped step leaves params, model state
    AND optimizer state exactly as if the step never ran)."""
    return tmap(lambda a, b: jnp.where(finite, a, b), new, old)


class _Staged:
    """A planned, device-placed K'-step block awaiting dispatch."""

    __slots__ = ("xs", "ys", "sizes", "lrs", "lrs_dev", "steps_dev",
                 "rngs_dev", "sync", "stage_s")

    def __init__(self, xs, ys, sizes, lrs, lrs_dev, steps_dev, rngs_dev,
                 sync, stage_s=0.0):
        self.xs, self.ys, self.sizes = xs, ys, sizes
        self.lrs, self.lrs_dev = lrs, lrs_dev
        self.steps_dev, self.rngs_dev = steps_dev, rngs_dev
        self.sync = sync  # a trigger/epoch/end boundary ends this block
        # host time inside stager.take — the interval of the tracer's
        # ``stage`` category and of metrics.time("data") (telemetry)
        self.stage_s = stage_s


class _InFlight:
    """A dispatched block whose per-step losses are still on device."""

    __slots__ = ("losses", "sizes", "lrs", "t0", "index", "stage_s",
                 "dispatch_s", "first_compile")

    def __init__(self, losses, sizes, lrs, t0, index=0, stage_s=0.0,
                 dispatch_s=0.0, first_compile=False):
        self.losses, self.sizes, self.lrs, self.t0 = losses, sizes, lrs, t0
        self.index = index            # dispatch index (telemetry: block=)
        self.stage_s = stage_s        # staging host time (telemetry)
        self.dispatch_s = dispatch_s  # jit enqueue host time (telemetry)
        self.first_compile = first_compile  # dispatch included a compile


class Optimizer:
    """Builder + the shared fused/pipelined driver loop."""

    def __init__(self, model: Module, dataset: AbstractDataSet,
                 criterion: Criterion, batch_size: Optional[int] = None):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.batch_size = batch_size

        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = max_epoch(1)
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: Sequence[ValidationMethod] = ()
        self.checkpoint_trigger: Optional[Trigger] = None
        self.checkpoint_path: Optional[str] = None
        self.overwrite_checkpoint = True
        # retention/async knobs (None = Config defaults); the manager is
        # built lazily so builder calls in any order all take effect
        self.checkpoint_keep_last: Optional[int] = None
        self.checkpoint_keep_every: Optional[int] = None
        self.checkpoint_async: Optional[bool] = None
        self.preemption_handling = False
        self._ckpt_manager: Optional[CheckpointManager] = None
        self._preemption: Optional[PreemptionHandler] = None
        self._resume_schema: Optional[dict] = None
        self.grad_clip: Optional[Callable] = None
        self.grad_clip_spec: Optional[tuple] = None
        self.train_summary = None
        self.validation_summary = None
        self.metrics = Metrics()
        self.seed = 1
        # K-step dispatch fusion; None = Engine/config default
        self.steps_per_dispatch: Optional[int] = None

        # driver state (reference: the state Table inside OptimMethod —
        # epoch/neval survive checkpoint/resume)
        self.state: dict = {"epoch": 0, "neval": 0,
                            "records_processed_this_epoch": 0}
        # telemetry (bigdl_tpu/telemetry): None = resolve from Config at
        # optimize(); set_telemetry overrides per run.  When enabled the
        # driver carries a DriverTelemetry in self._telemetry — tracer
        # spans per pipeline phase, recompile/stall/memory watchdogs —
        # all host-side and provably inert (no dispatch, no sync).
        self.telemetry_enabled: Optional[bool] = None
        self.telemetry_trace_path: Optional[str] = None
        self._telemetry: Optional[DriverTelemetry] = None
        # flight recorder (bigdl_tpu/telemetry/flight): None — the
        # provably-inert state — unless Config.flight_recorder_path is
        # set; resolved per run by _train_driver.  Driver events
        # (checkpoint commits, rollbacks, numeric-guard hits,
        # preemption, crashes) land there with the run's trace_id.
        self._flight = None
        # admin-plane source name, minted once per optimizer (stable
        # across this optimizer's runs, unique across optimizers)
        self._admin_name: Optional[str] = None
        self._eval_fwd = None  # cached jit'd eval forward
        self._resume_opt_state = None  # optimizer state restored on retry
        self.compute_dtype = None  # None = full f32; jnp.bfloat16 for MXU
        # activation-memory policy (set_activation_memory): "none" =
        # inert (bitwise-identical driver), else remat and/or bf16
        # activation storage for HBM-bound workloads.  None = setter
        # never called — Config.activation_memory answers
        # (_resolved_activation_memory)
        self.activation_memory: Optional[str] = None
        # numeric-failure policy (set_numeric_guard): "off" | "skip" |
        # "rollback" | "abort" — see bigdl_tpu/resilience/numeric.py.
        # None = setter never called; Config.numeric_guard /
        # BIGDL_TPU_NUMERIC_GUARD applies.
        self.numeric_guard: Optional[str] = None
        # fault injection (bigdl_tpu/resilience/faults): None unless a
        # Config.fault_plan is live — EVERY driver fault site below
        # guards on that, so the disabled path is byte-identical
        self._fault_injector: Optional[FaultInjector] = None
        self._guard_policy = "off"  # resolved per run by _train_driver
        self._dispatch_count = 0  # jit dispatches issued (observability)
        self._replaying = None    # dispatch index of the block in replay
        self._stager: Optional[DeviceBlockStager] = None
        self._epoch_size = 0
        # elastic training (bigdl_tpu/resilience/membership): None —
        # the provably-inert state — unless a membership fault clause
        # or DistriOptimizer.set_elastic() arms one.  Every membership
        # site below guards on that, so a plan-free run builds no
        # membership object and no roster check.
        self._membership: Optional[ClusterMembership] = None
        # monotonic() timestamp of the last MembershipChanged detection
        # — the resumed run observes resilience/resize_downtime_s from
        # it once the driver is staging again
        self._resize_t0: Optional[float] = None

    # ------------------------------------------------------------- builder
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        self.validation_trigger = trigger
        self.validation_methods = list(methods)
        if batch_size is not None:
            # re-batch: reference scripts pass a validation batch size
            # (Optimizer.setValidation(batchSize) overload)
            from bigdl_tpu.dataset.transformer import SampleToMiniBatch
            dataset = dataset >> SampleToMiniBatch(
                batch_size, drop_remainder=False)
        self.validation_dataset = dataset
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       keep_last: Optional[int] = None,
                       keep_every: Optional[int] = None,
                       async_save: Optional[bool] = None) -> "Optimizer":
        """Snapshot the FULL training state to ``path/model.<neval>``
        whenever ``trigger`` fires (reference ``setCheckpoint``, now
        backed by :mod:`bigdl_tpu.checkpoint`): atomic + checksummed,
        committed on a background writer (``async_save``, default
        ``Config.checkpoint_async``), retained per ``keep_last`` /
        ``keep_every`` (defaults ``Config.checkpoint_keep_last/
        _keep_every``)."""
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_keep_last = keep_last
        self.checkpoint_keep_every = keep_every
        self.checkpoint_async = async_save
        if self._ckpt_manager is not None:
            # stop the old manager's writer thread — reconfiguring must
            # not strand a parked daemon per call
            self._ckpt_manager.close(raise_errors=False)
        self._ckpt_manager = None  # rebuilt with the new settings
        return self

    def over_write_checkpoint(self, enabled: bool = True) -> "Optimizer":
        """Allow (default) or forbid overwriting an existing
        ``model.<neval>`` file — the reference's ``overWriteCheckpoint``
        flag, both directions now real: with ``enabled=False`` a
        colliding save raises ``FileExistsError`` instead of silently
        replacing the older run's snapshot."""
        self.overwrite_checkpoint = bool(enabled)
        if self._ckpt_manager is not None:
            self._ckpt_manager.overwrite = self.overwrite_checkpoint
        return self

    def set_preemption_handling(self, enabled: bool = True) -> "Optimizer":
        """Install a SIGTERM/SIGINT handler for the duration of
        ``optimize()``: on signal the driver finishes the in-flight
        block, writes one final synchronous snapshot to the checkpoint
        path, and returns cleanly with ``state["preempted"] = True``
        (requires ``set_checkpoint``).  Resume with :meth:`resume`."""
        self.preemption_handling = bool(enabled)
        return self

    # replay-boundary: run start — nothing is in flight before optimize()
    def resume(self, path: Optional[str] = None) -> bool:
        """Restore the latest VALID snapshot (corrupt/torn ones are
        skipped, never loaded) from the configured checkpoint directory
        into this optimizer: model params/state, optimizer state
        (schema-validated at ``optimize()``), driver counters, RNG seed
        and dataset shuffle position — the next ``optimize()`` continues
        mid-epoch exactly.  Returns False when no snapshot exists."""
        if not self.checkpoint_path:
            raise ValueError("resume() needs set_checkpoint(path, ...) "
                             "so there is a directory to resume from")
        mgr = self._checkpoint_manager()
        verified = path is None
        ckpt = path if path is not None else mgr.latest_valid()
        if ckpt is None:
            return False
        mgr.restore_into(self, ckpt, verified=verified)
        logger.info("resumed from %s (iteration %d)", ckpt,
                    self.state.get("neval", 0))
        return True

    def set_gradient_clipping_by_value(self, min_v: float,
                                       max_v: float) -> "Optimizer":
        self.grad_clip = lambda g: clip_by_value(g, min_v, max_v)
        # structured mirror of the closure: the grad_sync step clips
        # OWNED SLICES of the reduced gradient, so it needs the clip
        # kind/bounds, not an opaque pytree callable
        self.grad_clip_spec = ("value", min_v, max_v)
        return self

    def set_gradient_clipping_by_l2_norm(self, max_norm: float) -> "Optimizer":
        self.grad_clip = lambda g: clip_by_global_norm(g, max_norm)
        self.grad_clip_spec = ("norm", max_norm)
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        self.grad_clip = None
        self.grad_clip_spec = None
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.validation_summary = summary
        return self

    def set_seed(self, seed: int) -> "Optimizer":
        self.seed = seed
        return self

    def set_compute_dtype(self, dtype) -> "Optimizer":
        """Mixed precision: fwd/bwd in ``dtype`` (bf16 for the MXU), master
        params + optimizer update stay f32.  See utils/precision.py."""
        self.compute_dtype = dtype
        return self

    _ACTIVATION_POLICIES = ("none", "bf16", "dots", "full", "bf16+dots",
                            "bf16+full")

    def set_activation_memory(self, policy: Optional[str]) -> "Optimizer":
        """Trade MXU headroom for HBM traffic on workloads pinned to
        the memory wall (BENCH: hbm_floor_fraction > 0.9).

        ``policy``:

        - ``None`` / ``"none"`` — inert: the step function is built
          exactly as before (bitwise-identical loss sequence, same
          dispatch count).
        - ``"dots"`` — selective rematerialization via
          ``jax.checkpoint(policy=checkpoint_dots)``: matmul outputs
          are saved, everything elementwise is recomputed in the
          backward instead of round-tripping through HBM.
        - ``"full"`` — full rematerialization
          (``nothing_saveable``): only the step inputs are saved; the
          whole forward is recomputed during the backward.  Exact math
          — remat changes WHAT is stored, never what is computed, so
          the loss trajectory is unchanged to float rounding (XLA may
          fuse the recomputed chain differently).
        - ``"bf16"`` — bf16 activation storage: forward/backward
          compute (and therefore every stored activation) in bf16 via
          the mixed-precision loss path; master params, gradients as
          applied, and the optimizer update stay f32.  A no-op when
          ``set_compute_dtype(bf16)`` is already active.
        - ``"bf16+dots"`` / ``"bf16+full"`` — both.

        Only activation dtypes/remat change — never params or update
        math (gated in tests/test_pallas_kernels.py)."""
        if policy is not None and policy not in self._ACTIVATION_POLICIES:
            raise ValueError(
                f"activation memory policy must be one of "
                f"{self._ACTIVATION_POLICIES} or None, got {policy!r}")
        # an explicit None IS the inert policy, not "unset": it must
        # override a configure()/env value the same way "none" does
        # (self.activation_memory stays None only when this setter was
        # never called — the one state Config may fill)
        self.activation_memory = "none" if policy is None else policy
        return self

    def set_numeric_guard(self, policy: Optional[str]) -> "Optimizer":
        """Non-finite loss/gradient policy for this run (overrides
        ``Config.numeric_guard`` / ``BIGDL_TPU_NUMERIC_GUARD``):

        - ``None`` / ``"off"`` — inert: the step function and the
          replay fetch are built exactly as before (bitwise loss
          sequence, equal dispatch count; gated in
          tests/test_resilience.py).
        - ``"skip"`` — the jit'd step gates its own update: on a
          non-finite loss or gradient the params / model-state /
          optimizer-state updates are ``jnp.where``-selected away ON
          DEVICE (the dynamic-loss-scaling skip idiom), the step is
          counted in ``resilience/steps_skipped``, training continues.
        - ``"rollback"`` — the replay raises
          :class:`~bigdl_tpu.resilience.NonFiniteStepError`; the
          optimizer restores the latest VALID snapshot
          (``CheckpointManager.latest_valid``) and re-runs, bounded by
          ``Config.failure_retry_times`` — automatic loss-spike
          recovery (requires ``set_checkpoint``; refused loudly at
          ``optimize()`` otherwise).
        - ``"abort"`` — the run fails loudly at the exact iteration.

        The per-step finite flags ride the SAME one-block-behind fetch
        as the loss vector — no policy adds a host sync."""
        # explicit None IS the inert policy, not "unset" (the
        # set_activation_memory contract): it must override an
        # env-provided policy the same way "off" does
        self.numeric_guard = "off" if policy is None \
            else validate_policy(policy)
        return self

    def set_steps_per_dispatch(self, k: int) -> "Optimizer":
        """Fuse ``k`` consecutive train steps into one jit dispatch
        (``lax.scan`` over stacked microbatches).  Loss trajectory and
        trigger cadence are K-invariant; raise it when the per-step
        compute is small enough that host dispatch shows up in the step
        time (BENCH: PTB-LSTM, Wide&Deep)."""
        if int(k) < 1:
            raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
        self.steps_per_dispatch = int(k)
        return self

    def set_telemetry(self, enabled: bool = True,
                      trace_path: Optional[str] = None) -> "Optimizer":
        """Enable/disable the telemetry subsystem for this run
        (overrides ``Config.telemetry_enabled`` / ``BIGDL_TPU_TELEMETRY``).
        ``trace_path``: write the Chrome-trace JSON there when training
        ends (summarize with ``python -m tools.trace_report``)."""
        self.telemetry_enabled = bool(enabled)
        if trace_path is not None:
            self.telemetry_trace_path = trace_path
        return self

    def telemetry_snapshot(self) -> Optional[dict]:
        """Registry + watchdog snapshot of the (last) telemetry-enabled
        run; None when telemetry was off."""
        return self._telemetry.snapshot() if self._telemetry else None

    def set_state(self, state: dict) -> "Optimizer":
        """Resume driver state (epoch/neval) from a checkpoint."""
        self.state.update(state)
        return self

    # ------------------------------------------------------------ factory
    @staticmethod
    def create(model: Module, dataset: AbstractDataSet, criterion: Criterion,
               distributed: Optional[bool] = None, **kw):
        """(reference ``Optimizer.apply`` factories, ``Optimizer.scala:597+``)"""
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
        if distributed is None:
            distributed = jax.device_count() > 1
        cls = DistriOptimizer if distributed else LocalOptimizer
        return cls(model, dataset, criterion, **kw)

    def optimize(self) -> Module:
        raise NotImplementedError

    # ------------------------------------------------------------- shared
    def _resolved_activation_memory(self) -> str:
        """Per-run ``set_activation_memory`` wins; otherwise
        ``Config.activation_memory`` (a garbage env value fails loudly
        here, same as the setter would)."""
        if self.activation_memory is not None:
            return self.activation_memory
        from bigdl_tpu.utils.config import get_config
        policy = get_config().activation_memory
        if policy not in self._ACTIVATION_POLICIES:
            raise ValueError(
                f"Config.activation_memory {policy!r} must be one of "
                f"{self._ACTIVATION_POLICIES}")
        return policy

    def _resolved_numeric_guard(self) -> str:
        """Per-run ``set_numeric_guard`` wins; otherwise
        ``Config.numeric_guard`` (a garbage env value fails loudly
        here, same as the setter would)."""
        if self.numeric_guard is not None:
            return self.numeric_guard
        from bigdl_tpu.utils.config import get_config
        return validate_policy(get_config().numeric_guard,
                               source="Config.numeric_guard")

    def _loss_and_grad_fn(self):
        model, criterion = self.model, self.criterion
        policy = self._resolved_activation_memory()
        compute_dtype = self.compute_dtype
        if policy.startswith("bf16"):
            if compute_dtype is not None and compute_dtype != jnp.bfloat16:
                # refusing beats silently dropping the requested
                # storage downcast: an explicit non-bf16 compute dtype
                # contradicts a bf16 activation policy
                raise ValueError(
                    f"activation memory policy {policy!r} "
                    f"conflicts with set_compute_dtype({compute_dtype}) "
                    f"— bf16 activation storage IS bf16 compute; drop "
                    f"one of the two settings")
            # bf16 activation storage: stored residuals are bf16 because
            # the fwd/bwd compute is — params/update stay f32 by the
            # mixed-precision contract (utils/precision.py)
            compute_dtype = jnp.bfloat16
        if compute_dtype is not None:
            from bigdl_tpu.utils.precision import mixed_precision_loss_fn
            loss_fn = mixed_precision_loss_fn(model, criterion,
                                              compute_dtype)
        else:
            def loss_fn(params, mstate, x, y, rng):
                out, new_mstate = model.apply(params, mstate, x,
                                              training=True, rng=rng)
                return criterion.apply(out, y), new_mstate

        # per-layer L1/L2 penalties (reference Regularizer.scala applies
        # them inside accGradParameters; here they enter the loss so
        # jax.grad produces the identical gradient contribution)
        from bigdl_tpu.nn.regularizers import (has_regularizers,
                                               regularization_loss)
        if has_regularizers(model):
            base = loss_fn

            def loss_fn(params, mstate, x, y, rng, _base=base):
                loss, new_mstate = _base(params, mstate, x, y, rng)
                return loss + regularization_loss(model, params), \
                    new_mstate

        if policy.endswith("dots") or policy.endswith("full"):
            # selective remat over the whole loss computation: "dots"
            # saves matmul outputs and recomputes the elementwise chain
            # in the backward; "full" saves only the step inputs.
            # Exact math either way — only the residual set changes.
            remat_policy = (jax.checkpoint_policies.dots_saveable
                            if policy.endswith("dots") else
                            jax.checkpoint_policies.nothing_saveable)
            loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)

        return jax.value_and_grad(loss_fn, has_aux=True)

    def _fast_forward(self, data_iter, state):
        """Mid-epoch resume: skip the samples already processed this epoch
        so the epoch boundary (and shuffle cadence) stays correct
        (reference: recordsProcessedThisEpoch in the OptimMethod state
        table, ``DistriOptimizer.scala:124-134``).

        ``records_processed_this_epoch`` counts GLOBAL records (the
        replay adds ``n_local * scale``); the iterator here yields this
        host's LOCAL batches, so the skip budget is the global count
        divided back by the records scale (process_count under
        multi-host SPMD — every host skips its own 1/P share).  The
        counter must divide EVENLY: under an elastic resume P may have
        changed since the snapshot, and a remainder means this host's
        share is not expressible in whole records — silently flooring
        would mis-position the dataset (the PR-7 fix assumed a
        constant P)."""
        scale = max(1, self._records_scale())
        rec = state.get("records_processed_this_epoch", 0)
        if rec % scale:
            raise ValueError(
                f"mid-epoch resume: the snapshot's global records "
                f"counter ({rec}) does not divide by this run's records "
                f"scale ({scale}) — the world size/process count "
                f"changed since the snapshot was written and the "
                f"per-host skip would mis-position the dataset; resume "
                f"at a compatible scale or from an epoch boundary")
        skip = rec // scale
        from bigdl_tpu.dataset.prefetch import fast_forward_records
        skipped = fast_forward_records(data_iter, skip)
        if skipped:
            logger.info("resume: skipped %d already-processed local "
                        "records (of %d global)", skipped, skip * scale)

    def _tel_span(self, name: str, cat: str, **args):
        """Tracer span when telemetry is on; shared no-op otherwise —
        the off path allocates nothing."""
        tel = self._telemetry
        if tel is None:
            return NULL_SPAN
        return tel.tracer.span(name, cat=cat, **args)

    def _flight_event(self, event: str, **fields) -> None:
        """Record one driver event in the flight recorder (no-op when
        none is live), carrying the run's trace context when telemetry
        is on — the join key ``tools/obs_report.py`` correlates by."""
        fl = self._flight
        if fl is not None:
            tel = self._telemetry
            fl.record(event, cat="driver",
                      trace_id=(tel.trace_id if tel is not None
                                else None), **fields)

    def _checkpoint_manager(self) -> CheckpointManager:
        if self._ckpt_manager is None:
            from bigdl_tpu.utils.config import get_config
            cfg = get_config()
            pick = lambda v, d: d if v is None else v  # noqa: E731
            self._ckpt_manager = CheckpointManager(
                self.checkpoint_path,
                keep_last=pick(self.checkpoint_keep_last,
                               cfg.checkpoint_keep_last),
                keep_every=pick(self.checkpoint_keep_every,
                                cfg.checkpoint_keep_every),
                overwrite=self.overwrite_checkpoint,
                async_save=pick(self.checkpoint_async,
                                cfg.checkpoint_async),
                registry=self.metrics.registry)
        return self._ckpt_manager

    def _checkpoint_schema(self, params) -> dict:
        """Manifest schema of THIS run's training state (the SPMD
        subclass adds the grad_sync bucket plan)."""
        return build_schema(
            params, optim_method=type(self.optim_method).__name__)

    def _model_params_schema(self) -> Optional[dict]:
        """Shape/dtype fingerprint of THIS model's params — live params
        when present, else ``jax.eval_shape`` over init (no compute) —
        so ``CheckpointManager.restore_into`` can refuse an
        architecture-drifted snapshot BEFORE overwriting the model."""
        from bigdl_tpu.checkpoint.schema import describe_params
        if self.model._params is not None:
            return describe_params(self.model._params)
        try:
            shapes = jax.eval_shape(
                lambda r: self.model.init(r)[0], jax.random.PRNGKey(0))
        except Exception:  # init not eval_shape-able: the full-schema
            return None    # check at optimize() still runs
        return describe_params(shapes)

    def _validate_resume_schema(self, params) -> None:
        """Diff the restored snapshot's schema against this run —
        grad_sync flips, bucket-plan drift, and architecture drift fail
        loudly here instead of as a jit structure error.  An elastic
        run validates in elastic-compat mode: world-size/bucket-padding
        drift is the point, logical identity stays strict."""
        saved, self._resume_schema = self._resume_schema, None
        if saved is not None:
            validate_schema(saved, self._checkpoint_schema(params),
                            elastic=self._membership is not None)

    def _arm_membership_from_plan(self, faults) -> None:
        """Arm the membership layer when the fault plan carries
        ``resize``/``host_loss``/``device_loss`` clauses.  The base
        (single-device) trainer cannot resize — membership clauses in
        its plan are a configuration error, refused loudly instead of
        silently never firing.  DistriOptimizer overrides with the real
        arming (mesh roster → ClusterMembership)."""
        if faults is None or not faults.has_membership_kinds():
            return
        raise ValueError(
            "fault plan contains membership kinds (resize/host_loss/"
            "device_loss) but this is a LocalOptimizer — elastic "
            "training needs DistriOptimizer's device mesh to resize "
            "over")

    def _apply_membership_clause(self, clause) -> None:
        """Translate one fired membership fault clause into the
        corresponding ClusterMembership signal (the injector stays free
        of roster knowledge)."""
        m = self._membership
        if clause.kind == "resize":
            m.request_resize(clause.to)
        elif clause.kind == "host_loss":
            m.signal_host_loss(to=clause.to)
        else:  # device_loss
            m.signal_device_loss(to=clause.to)

    def _maybe_checkpoint(self, params, mstate, ostate):
        # the trigger reads only driver counters, which advance in
        # lockstep on every process (the replay adds the same global
        # increments)  # replicated-by: lockstep-driver-counters
        if self.checkpoint_trigger and self.checkpoint_path \
                and self.checkpoint_trigger(self.state):
            with self._tel_span("checkpoint", "trigger",
                                neval=self.state["neval"],
                                block=self._replaying):
                self._do_checkpoint(params, mstate, ostate)

    def _do_checkpoint(self, params, mstate, ostate,
                       sync: bool = False) -> None:
        """Snapshot the full training state at the CURRENT replayed
        iteration.  Called only at replay boundaries, where the
        one-block-behind loss fetch has already synced the producing
        block — the capture inside ``CheckpointManager.save`` is a
        D2H copy, never a pipeline drain (GL107 discipline)."""
        # spmdcheck: checkpoint capture gathers sharded state — every
        # process must reach it at the same replayed iteration
        spmdcheck.note("checkpoint", payload=params)
        mgr = self._checkpoint_manager()
        pos = getattr(self.dataset, "position_state", None)
        run_state = {"seed": self.seed,
                     "dataset_position": pos() if pos is not None else None}
        mgr.save(self.state["neval"], params, mstate, ostate,
                 driver_state=dict(self.state), run_state=run_state,
                 schema=self._checkpoint_schema(params), sync=sync)

    def _run_validation(self, params, mstate) -> Optional[dict]:
        # same lockstep counters as the checkpoint trigger: validation
        # (a collective under multi-host eval) fires on every process
        # or none  # replicated-by: lockstep-driver-counters
        if not (self.validation_trigger and self.validation_methods
                and self.validation_dataset is not None
                and self.validation_trigger(self.state)):
            return None
        with self._tel_span("validation", "trigger",
                            neval=self.state["neval"],
                            block=self._replaying):
            results = self.evaluate_with(params, mstate)
        for name, res in results.items():
            logger.info("validation %s = %s", name, res)
            if self.validation_summary is not None:
                self.validation_summary.add_scalar(name, res.result,
                                                   self.state["neval"])
        # expose primary score to triggers; feed metric-driven schedules
        # (Plateau) exactly once per validation — NOT once per iteration
        first = next(iter(results.values()))
        self.state["score"] = first.result
        sched = self.optim_method.learning_rate_schedule
        if sched is not None and hasattr(sched, "record"):
            sched.record(first.result)
        return results

    # ------------------------------------------------- train-loop hooks
    # DistriOptimizer overrides these to shard the work over the mesh;
    # the driver loop itself lives only here.
    def _place_train_block(self, xs, ys):
        """Host-stacked (K, batch, ...) trees → device arrays."""
        xs = tmap(jnp.asarray, xs)
        ys = None if ys is None else tmap(jnp.asarray, ys)
        return xs, ys

    def _records_scale(self) -> int:
        """Host-local batch rows → global records (process_count under
        multi-host SPMD)."""
        return 1

    def _constrain_step_outputs(self, params, ostate):
        """Inside the jit'd step, after the optimizer update — the SPMD
        subclass pins output shardings here."""
        return params, ostate

    def _log_train_iteration(self, lr: float) -> None:
        # reference per-iteration log line (DistriOptimizer.scala:388-394)
        s = self.state
        logger.info(
            "epoch %d iter %d loss %.4f lr %.5g throughput %.1f rec/s",
            s["epoch"], s["neval"], s["loss"], lr, s["throughput"])

    def _log_parameter_histograms(self, params) -> None:
        """Trigger-gated per-parameter summaries (SPMD subclass)."""

    # --------------------------------------------------- fused train step
    def _block_body(self, one_step, k: int):
        """Wrap ``one_step(params, mstate, ostate, x, y, lr, step, rng)``
        into the K-block calling convention every block fn shares:
        ``k == 1`` squeezes the leading step axis off ``xs``/``ys`` and
        returns the loss as a length-1 vector; ``k > 1`` runs the step
        under ``lax.scan``.  The returned per-step loss vector is what
        ``_replay_block`` consumes — this wrapper is the ONE place that
        encodes the convention (the SPMD grad_sync block builds on the
        same body, inside a shard_map)."""
        if k == 1:
            def body(params, mstate, ostate, xs, ys, lrs, steps, rngs):
                x = tmap(lambda a: a[0], xs)
                y = None if ys is None else tmap(lambda a: a[0], ys)
                params, mstate, ostate, out = one_step(
                    params, mstate, ostate, x, y, lrs[0], steps[0],
                    rngs[0])
                # `out` is the loss scalar — or (loss, finite) under a
                # live numeric guard; either way every leaf grows the
                # length-1 step axis the replay convention expects
                return params, mstate, ostate, tmap(lambda l: l[None],
                                                    out)
            return body

        def body(params, mstate, ostate, xs, ys, lrs, steps, rngs):
            def scan_body(carry, inp):
                params, mstate, ostate = carry
                x, y, lr, step, rng = inp
                params, mstate, ostate, loss = one_step(
                    params, mstate, ostate, x, y, lr, step, rng)
                return (params, mstate, ostate), loss

            (params, mstate, ostate), losses = jax.lax.scan(
                scan_body, (params, mstate, ostate),
                (xs, ys, lrs, steps, rngs))
            return params, mstate, ostate, losses
        return body

    def _build_block_fn(self, grad_fn, k: int):
        """One jit'd dispatch covering ``k`` consecutive train steps.

        ``k == 1`` stays a straight-line step (identical HLO to the
        classic per-iteration dispatch, minus a leading-axis squeeze);
        ``k > 1`` runs the step under ``lax.scan`` so XLA sees one
        program — no per-iteration dispatch, and donated
        params/mstate/ostate update in place across the whole block.
        Inputs: ``xs``/``ys`` carry a leading ``k`` step axis (sharded
        over `data` on axis 1 in the SPMD path); ``lrs``/``steps``/
        ``rngs`` are per-step vectors so host-side LR schedules never
        retrace.  Returns the per-step loss vector — every iteration
        stays observable to triggers and summaries."""
        grad_clip = self.grad_clip
        optim = self.optim_method
        constrain = self._constrain_step_outputs
        guard = self._resolved_numeric_guard()

        def one_step(params, mstate, ostate, x, y, lr, step, rng):
            (loss, new_mstate), grads = grad_fn(params, mstate, x, y, rng)
            if grad_clip is not None:
                grads = grad_clip(grads)
            if guard == "off":
                # byte-identical to the pre-guard step — the provably
                # inert state (gated in tests/test_resilience.py)
                params, ostate = optim.update(grads, params, ostate, lr,
                                              step)
                params, ostate = constrain(params, ostate)
                return params, new_mstate, ostate, loss
            finite = step_finite(loss, grads)
            new_params, new_ostate = optim.update(grads, params, ostate,
                                                  lr, step)
            new_params, new_ostate = constrain(new_params, new_ostate)
            if guard == "skip":
                # gate the whole update on device: a non-finite step
                # leaves params/mstate/ostate exactly as before it
                return (select_step(finite, new_params, params),
                        select_step(finite, new_mstate, mstate),
                        select_step(finite, new_ostate, ostate),
                        (loss, finite))
            # rollback/abort: update as usual, just report the flag —
            # the replay raises at the exact iteration and recovery
            # discards these params anyway
            return new_params, new_mstate, new_ostate, (loss, finite)

        return jax.jit(self._block_body(one_step, k),
                       donate_argnums=(0, 1, 2))

    # ------------------------------------------------------ driver loop
    def _train_driver(self, params, mstate, ostate, grad_fn, rng):
        """The shared training loop (see module docstring for the
        fusion/pipelining design).  Returns the final (params, mstate,
        ostate) bindings."""
        state = self.state
        k_max = self.steps_per_dispatch or Engine.steps_per_dispatch()
        k_max = max(1, int(k_max))
        scale = self._records_scale()
        # telemetry: resolve the enable knob (per-run override → config),
        # share the Metrics registry so phase accumulators + watchdog
        # counters land in one snapshot.  self._telemetry stays None when
        # off — every call site below is gated on that, so the disabled
        # path is byte-identical to the pre-telemetry driver.
        from bigdl_tpu.utils.config import get_config
        cfg = get_config()
        tel_on = (self.telemetry_enabled if self.telemetry_enabled
                  is not None else cfg.telemetry_enabled)
        # flight recorder: None (inert) unless Config.flight_recorder_
        # path is set — every driver event site guards on that
        from bigdl_tpu.telemetry import flight as _flight_mod
        self._flight = _flight_mod.from_config()
        tel = None
        if tel_on:
            tel = self._telemetry = DriverTelemetry(
                registry=self.metrics.registry,
                trace_capacity=cfg.telemetry_trace_capacity,
                trace_path=(self.telemetry_trace_path
                            or cfg.telemetry_trace_path or None),
                flight=self._flight)
        else:
            # drop any bundle from a previous enabled run on this
            # optimizer — _tel_span/_replay_block read self._telemetry,
            # so a stale one would keep recording through an "off" run
            self._telemetry = None
        # admin plane: config-driven (admin_port=0 → None, no thread);
        # the driver registry, tracer, and watchdog verdicts become
        # scrape-able while the run is live.  The source name is
        # unique-per-optimizer (stable across this optimizer's runs) so
        # concurrent drivers don't overwrite each other's registration.
        from bigdl_tpu.telemetry import admin as _admin
        _srv = _admin.maybe_start()
        if _srv is not None:
            if getattr(self, "_admin_name", None) is None:
                self._admin_name = _srv.unique_source_name("driver")
            _srv.add_registry(self._admin_name, self.metrics.registry)
            if tel is not None:
                _srv.add_tracer(self._admin_name, tel.tracer)
                _srv.add_health(self._admin_name, tel.health_snapshot)
            else:
                # a telemetry-off rerun on this optimizer must not
                # leave the PREVIOUS run's tracer/health serving as
                # current — /healthz would report a dead run's
                # watchdog verdicts
                _srv.drop_tracer(self._admin_name)
                _srv.drop_health(self._admin_name)
            if self._flight is not None:
                _srv.set_flight(self._flight)
        # resilience: the numeric-guard policy this run's block fns and
        # replay share, and the fault injector (None — the provably
        # inert state — unless Config.fault_plan is live; every site
        # below guards on that)
        guard = self._guard_policy = self._resolved_numeric_guard()
        if guard == "rollback" and not self.checkpoint_path:
            raise ValueError(
                "numeric_guard='rollback' needs set_checkpoint(path, "
                "trigger) — there is no snapshot to roll back to")
        from bigdl_tpu.utils.config import get_config
        cfg_plan = get_config().fault_plan or ""
        if self._fault_injector is not None \
                and self._fault_injector.plan != cfg_plan:
            # the configured plan CHANGED since this injector was
            # built (a reused optimizer across configure() calls) —
            # honor the knob, including clearing it back to inert
            self._fault_injector = None
        if self._fault_injector is None and cfg_plan:
            # built once per (optimizer, plan), not per attempt: a
            # fault plan describes one timeline of the outside world,
            # so clause firing budgets (count=) must survive the
            # rollback/retry loops re-entering this driver
            self._fault_injector = FaultInjector.from_config(
                registry=self.metrics.registry)
            logger.warning("fault injection live: %s",
                           self._fault_injector.describe())
        faults = self._fault_injector
        # elastic membership: armed only when the plan carries
        # membership kinds or set_elastic() was called — otherwise
        # self._membership stays None and every site below is inert
        self._arm_membership_from_plan(faults)
        membership = self._membership
        if membership is not None and not self.checkpoint_path:
            raise ValueError(
                "elastic training (membership fault kinds / "
                "set_elastic) needs set_checkpoint(path, trigger) — a "
                "resize resumes from the latest valid snapshot")
        # the epoch this driver run dispatches under; the loop compares
        # it against the live epoch at the replay boundary it already
        # crosses — detection costs zero additional host syncs
        run_epoch = membership.epoch() if membership is not None else 0
        # checkpointing: manager built up front so the stall-fraction
        # denominator starts at the run, and preemption (SIGTERM/SIGINT
        # → finish block + final snapshot + clean return) has somewhere
        # to write.  Both are inert when unconfigured.  A previous
        # run's preempted verdict must not leak into this run's state
        # (or its checkpoints).
        state.pop("preempted", None)
        mgr: Optional[CheckpointManager] = None
        if self.checkpoint_path:
            mgr = self._checkpoint_manager()
            mgr.mark_run_start()
            # the manager outlives runs (cached) — stamp THIS run's
            # flight recorder + trace context so its commit events
            # correlate with this run's trace
            mgr.flight = self._flight
            mgr.trace_id = tel.trace_id if tel is not None else None
        epoch_size = self._epoch_size = self.dataset.size()
        data_iter = self.dataset.data(train=True)
        self._fast_forward(data_iter, state)
        stager = DeviceBlockStager(data_iter, self._place_train_block,
                                   tracer=tel.tracer if tel else None,
                                   registry=tel.registry if tel else None)
        self._stager = stager
        if self._resize_t0 is not None:
            # this run is the elastic resume: the driver is about to
            # stage again — the detection→here window is the measured
            # resize downtime
            downtime = time.monotonic() - self._resize_t0
            self._resize_t0 = None
            self.metrics.registry.histogram(
                "resilience/resize_downtime_s").observe(downtime)
            self._flight_event("resize_resumed",
                               downtime_s=round(downtime, 4),
                               iteration=state["neval"],
                               epoch=run_epoch)
        # the Parameters-histogram summary trigger is probed too: its
        # firing iteration must end a sync block so the histogram sees
        # exactly that iteration's params, not the end-of-block binding
        param_trig = getattr(self.train_summary, "trigger_for",
                             lambda _n: None)("Parameters") \
            if self.train_summary is not None else None
        triggers = (self.validation_trigger, self.checkpoint_trigger,
                    self.end_when, param_trig)
        block_fns: dict = {}
        self._dispatch_count = 0
        bsz_hint = 0
        # planning counters: where the driver state WILL be once every
        # dispatched block has been replayed (at most one block ahead)
        p_neval = state["neval"]
        p_epoch = state["epoch"]
        p_records = state["records_processed_this_epoch"]

        def stage_next():
            """Plan (trigger probe + epoch budget) and stage one block.
            Runs right after a dispatch, so the host stacking and the
            asynchronous host→device transfer overlap the in-flight
            block's compute — the double buffer.  The whole body is one
            top-level ``stage_next`` span carrying the index the block
            will have at its dispatch."""
            nonlocal bsz_hint
            with self._tel_span("stage_next", "stage_next",
                                block=self._dispatch_count):
                with self._tel_span("plan", "plan"):
                    probe_state = dict(state)
                    probe_state.update(
                        neval=p_neval, epoch=p_epoch,
                        records_processed_this_epoch=p_records)
                    fire = probe_fire_step(probe_state, k_max,
                                           bsz_hint * scale, epoch_size,
                                           triggers)
                    k_plan = fire if fire is not None else k_max
                    budget = max(1, -(-(epoch_size - p_records) // scale))
                t_take0 = time.perf_counter()
                with self.metrics.time("data"):
                    xs, ys, sizes = stager.take(k_plan, budget)
                stage_s = time.perf_counter() - t_take0
                k = len(sizes)
                if faults is not None:
                    # batch-poison fault site (corrupt_batch/
                    # nonfinite_grads clauses, keyed by global iteration
                    # number) — only ever reached with a live plan
                    xs = faults.corrupt_staged(xs, p_neval, k)
                bsz_hint = sizes[0]
                # the eager device calls below queue behind the running
                # block, so this span is where a device-bound driver
                # waits outside ``device_wait``
                with self._tel_span("step_args", "step_args", k=k):
                    # per-step host scalars, one current_lr call per
                    # iteration in order (schedules and the retry tests
                    # rely on that cadence)
                    lrs = [float(self.optim_method.current_lr(
                        p_neval + j, p_epoch)) for j in range(k)]
                    # per-step dropout keys are a PURE FUNCTION of (run
                    # key, iteration number) — fold_in, not sequential
                    # splits — so a mid-epoch resume re-derives exactly
                    # the keys the uninterrupted run used (bitwise-resume
                    # contract of bigdl_tpu.checkpoint), and the
                    # derivation is K-invariant
                    keys = [jax.random.fold_in(rng, p_neval + j)
                            for j in range(k)]
                    lrs_dev = jnp.asarray(np.asarray(lrs, np.float32))
                    steps_dev = jnp.asarray(np.arange(
                        p_neval, p_neval + k, dtype=np.int32))
                    rngs_dev = jnp.stack(keys)
                ends_epoch = p_records + sum(sizes) * scale >= epoch_size
                sync = ends_epoch or fire == k
                return _Staged(xs, ys, sizes, lrs, lrs_dev, steps_dev,
                               rngs_dev, sync, stage_s=stage_s)

        pending: Optional[_InFlight] = None
        staged: Optional[_Staged] = None
        # installed LAST, immediately before the try whose finally
        # uninstalls — an exception anywhere in run setup must never
        # leave the process with hijacked (flag-only) signal handlers
        preempt = None
        if self.preemption_handling and mgr is not None:
            preempt = self._preemption = PreemptionHandler()
            preempt.install()
        try:
            while True:
                # the scheduler evicts the whole slice at once — every
                # host's grace window opens together, so polling the
                # flag at block granularity stays uniform
                # replicated-by: pod-eviction-broadcast
                if preempt is not None and preempt.triggered:
                    # preemption: finish the in-flight block (replay
                    # syncs it — params/state land on an exact block
                    # boundary the uninterrupted run also hits), write
                    # ONE final synchronous snapshot, return cleanly.
                    # The planned-ahead `staged` block is discarded; its
                    # batches are re-derived on resume from the saved
                    # shuffle position + records counter.
                    if pending is not None:
                        self._replay_block(pending, params, mstate,
                                           ostate)
                        pending = None
                    logger.warning(
                        "preemption signal: final snapshot at iteration "
                        "%d, exiting cleanly", state["neval"])
                    # flag-only handler fired; the heavy work (and this
                    # event) runs here on the driver thread — writing
                    # from a signal handler is how dumps get torn
                    self._flight_event("preemption",
                                       iteration=state["neval"])
                    mgr.wait()  # writer idle → no concurrent GC below
                    # every process records the step when a multi-host
                    # checkpoint commits (the PR-7 mirror write in
                    # DistriOptimizer._do_checkpoint), so this dedup
                    # cannot send hosts down different sides of the
                    # allgather  # replicated-by: checkpoint-step-mirror
                    if mgr.last_saved_step != state["neval"]:
                        # a trigger checkpoint that fired on this very
                        # iteration already covers it — don't burn the
                        # grace window on a redundant serialize+fsync
                        # (or trip over_write_checkpoint(False))
                        self._do_checkpoint(params, mstate, ostate,
                                            sync=True)
                    state["preempted"] = True
                    break
                if membership is not None:
                    changed = membership.changed_since(run_epoch)
                    if changed is not None:
                        # resize-on-preemption, riding the replay
                        # boundary the loop already crossed: graceful
                        # changes (resize request / preemption warning)
                        # finish the in-flight block and write a final
                        # synchronous snapshot (PR-7 semantics, zero
                        # steps lost); abrupt device loss abandons it —
                        # the device buffers are gone by assumption —
                        # and the resume pays the steps since the last
                        # snapshot.  The planned-ahead `staged` block is
                        # discarded either way; its batches re-derive
                        # from the saved records counter.
                        t_detect = time.monotonic()
                        if changed.graceful:
                            if pending is not None:
                                self._replay_block(pending, params,
                                                   mstate, ostate)
                                pending = None
                            mgr.wait()  # writer idle → no racing GC
                            # same mirror contract as the preemption
                            # dedup above (see DistriOptimizer.
                            # _do_checkpoint's non-zero-process write)
                            # replicated-by: checkpoint-step-mirror
                            if mgr.last_saved_step != state["neval"]:
                                self._do_checkpoint(params, mstate,
                                                    ostate, sync=True)
                        else:
                            pending = None
                        logger.warning(
                            "membership epoch %d (world %d, %s): "
                            "suspending at iteration %d for elastic "
                            "resume", changed.epoch, changed.world,
                            changed.reason, state["neval"])
                        self._flight_event(
                            "membership_change", epoch=changed.epoch,
                            world=changed.world, reason=changed.reason,
                            graceful=changed.graceful,
                            iteration=state["neval"])
                        raise MembershipChanged(
                            changed, changed.graceful, state["neval"],
                            t_detect)
                if staged is None:
                    if pending is None and self.end_when(state):
                        break
                    staged = stage_next()
                k = len(staged.sizes)
                fn = block_fns.get(k)
                new_fn = fn is None
                if new_fn:
                    fn = block_fns[k] = self._build_block_fn(grad_fn, k)
                # spmdcheck: the fused block is one SPMD program — every
                # process must dispatch the same block shape in the same
                # order or the in-step collectives go one-sided
                spmdcheck.note("dispatch", axis=f"k{k}", payload=staged.xs)
                t0 = time.perf_counter()
                with self._tel_span("dispatch", "dispatch", k=k,
                                    compile=new_fn,
                                    block=self._dispatch_count):
                    if faults is None:
                        params, mstate, ostate, losses = fn(
                            params, mstate, ostate, staged.xs, staged.ys,
                            staged.lrs_dev, staged.steps_dev,
                            staged.rngs_dev)
                    else:
                        # dispatch fault site + bounded retry-with-
                        # backoff: the injector fires BEFORE the jit
                        # call, so a retried attempt still owns every
                        # donated buffer (a post-donation error is not
                        # transiently retryable — the inputs are gone)
                        params, mstate, ostate, losses = \
                            self._dispatch_with_retry(
                                lambda: fn(params, mstate, ostate,
                                           staged.xs, staged.ys,
                                           staged.lrs_dev,
                                           staged.steps_dev,
                                           staged.rngs_dev),
                                self._dispatch_count)
                self._dispatch_count += 1
                if tel is not None:
                    # recompile watchdog: the first compile of each block
                    # length k is the planned one; cache growth after
                    # that is a steady-state retrace (GL106 at runtime)
                    tel.recompile.observe(("block_fn", k),
                                          jit_cache_size(fn))
                block = _InFlight(losses, staged.sizes, staged.lrs, t0,
                                  index=self._dispatch_count - 1,
                                  stage_s=staged.stage_s,
                                  dispatch_s=time.perf_counter() - t0,
                                  first_compile=new_fn)
                p_neval += k
                p_records += sum(staged.sizes) * scale
                if p_records >= epoch_size:
                    p_epoch += 1
                    p_records = 0
                sync = staged.sync
                # double-buffer: next block's H2D lands while this one
                # runs (a sync block ends at a boundary the replay must
                # handle — shuffle/validation/stop — before any further
                # staging)
                staged = stage_next() if not sync else None
                if pending is not None:
                    ended = self._replay_block(pending, params, mstate,
                                               ostate)
                    pending = None
                    if ended:
                        break
                if sync:
                    if self._replay_block(block, params, mstate, ostate):
                        break
                else:
                    pending = block
        finally:
            run_failing = sys.exc_info()[0] is not None
            if run_failing:
                etype = sys.exc_info()[0]
                if not (isinstance(etype, type)
                        and issubclass(etype, MembershipChanged)):
                    # the black box's raison d'être: the crash is on
                    # disk (the recorder flushes per event) even if
                    # nothing below gets to run.  A membership change
                    # is a measured event, not a crash — it already
                    # recorded membership_change above.
                    self._flight_event("run_crash",
                                       error=getattr(etype, "__name__",
                                                     str(etype)),
                                       iteration=state["neval"])
            if preempt is not None:
                preempt.uninstall()
            if tel is not None:
                # dump the Chrome trace even on an interrupted run — a
                # crash timeline is precisely when you want the trace
                tel.finalize()
            if mgr is not None:
                # drain pending async snapshot writes so optimize()
                # returning means the checkpoints EXIST; a deferred
                # write error fails the run loudly — unless the run is
                # already failing (don't mask the original exception)
                try:
                    mgr.wait()
                except Exception:
                    if not run_failing:
                        raise
                    logger.exception(
                        "async checkpoint write also failed during "
                        "teardown of an already-failing run")
        return params, mstate, ostate

    def _on_nonfinite_step(self, j: int, losses) -> None:
        """One replayed iteration carried a non-finite loss/grad flag.
        ``skip``: the update was already gated away on device — count
        it and move on.  ``rollback``/``abort``: raise at the exact
        iteration (rollback is caught by the optimize() recovery loop,
        abort surfaces to the caller).  Reports the 0-based global step
        index — the same index fault plans (``corrupt_batch@at=N``) and
        lr schedules see, one less than the just-incremented
        ``state["neval"]`` completion count."""
        policy = self._guard_policy
        step = self.state["neval"] - 1
        reg = self.metrics.registry
        reg.counter("resilience/nonfinite_steps").inc()
        if policy == "skip":
            reg.counter("resilience/steps_skipped").inc()
            if self._telemetry is not None:
                self._telemetry.tracer.instant(
                    "nonfinite_step_skipped", cat="resilience",
                    step=step)
            self._flight_event("nonfinite_step", step=step,
                               policy="skip", loss=float(losses[j]))
            logger.warning(
                "non-finite step at iteration %d (loss=%s) — update "
                "skipped on device", step, float(losses[j]))
            return
        self._flight_event("nonfinite_step", step=step, policy=policy,
                           loss=float(losses[j]))
        raise NonFiniteStepError(step, float(losses[j]), policy)

    # replay-boundary: the failed block is torn down before the restore
    def _rollback_nonfinite(self, e: NonFiniteStepError,
                            attempts: int, retry_budget: int) -> None:
        """``numeric_guard="rollback"`` recovery shared by both
        drivers: restore the latest VALID snapshot, or re-raise ``e``
        (policy isn't rollback, budget spent, no checkpointing, or
        nothing valid on disk).  The ``resilience/rollbacks`` counter
        is bumped only once a restorable snapshot is in hand — it
        audits restores that actually happened."""
        if e.policy != "rollback":
            raise e
        if attempts > retry_budget or not self.checkpoint_path:
            raise e
        mgr = self._checkpoint_manager()
        mgr.wait()  # writer idle: see every committed snapshot
        ckpt = mgr.latest_valid()
        if ckpt is None:
            raise e
        self.metrics.registry.counter("resilience/rollbacks").inc()
        if self._telemetry is not None:
            self._telemetry.tracer.instant(
                "rollback", cat="resilience", step=e.step, ckpt=ckpt)
        self._flight_event("rollback", step=e.step, ckpt=ckpt,
                           attempt=attempts)
        logger.warning(
            "non-finite step at iteration %d; rollback %d/%d from %s",
            e.step, attempts, retry_budget, ckpt)
        mgr.restore_into(self, ckpt, verified=True)

    def _dispatch_with_retry(self, fire, index: int):
        """Bounded retry-with-backoff around one block dispatch, only
        reached when fault injection is live.  The injector's driver
        site raises BEFORE ``fire()`` runs, so a retried attempt still
        owns the donated buffers; ``InjectedFault`` is transient by
        construction, so retrying it is exactly the degradation path a
        real transient dispatch failure (preempted ICI, momentary
        RESOURCE_EXHAUSTED) would take."""
        from bigdl_tpu.utils.config import get_config
        retries = get_config().failure_retry_times
        faults = self._fault_injector
        attempt = 0
        while True:
            try:
                faults.driver_dispatch(index)
                return fire()
            except InjectedFault:
                attempt += 1
                self.metrics.registry.counter(
                    "resilience/dispatch_retries").inc()
                if attempt > retries:
                    raise
                backoff = min(0.01 * (2.0 ** (attempt - 1)), 1.0)
                logger.warning(
                    "transient dispatch failure at dispatch %d; retry "
                    "%d/%d in %.0f ms", index, attempt, retries,
                    backoff * 1e3)
                time.sleep(backoff)

    def _replay_block(self, block: _InFlight, params, mstate, ostate):
        """Fetch a dispatched block's per-step losses (the driver's only
        device→host sync — one block behind the dispatch on the steady
        path) and replay its iterations through the driver state:
        per-iteration logging/summaries, epoch rollover (shuffle + fresh
        iterator, exactly as the unfused loop did), validation and
        checkpoint triggers at their exact iteration numbers, and the
        end_when check.  Returns True when training should stop."""
        tel = self._telemetry
        self._replaying = block.index
        t_wait0 = time.perf_counter()
        # spmdcheck: the fetch syncs the producing block on every
        # process — a one-sided fetch deadlocks the block's collectives
        spmdcheck.note("block_fetch", payload=block.losses)
        with self.metrics.time("computing"), \
                self._tel_span("device_wait", "device_wait",
                               steps=len(block.sizes), block=block.index):
            # the driver's one and only device→host sync: the
            # one-block-behind loss fetch (GL107-safe — the span wraps
            # the fetch the driver already performs, never adds one).
            # Under a live numeric guard the block returns
            # (losses, finite_flags) — the flags ride the SAME fetch,
            # so no policy adds a sync
            fetched = jax.device_get(block.losses)
        t_wait1 = time.perf_counter()
        if isinstance(fetched, tuple):
            losses, finite = np.asarray(fetched[0]), np.asarray(fetched[1])
        else:
            losses, finite = np.asarray(fetched), None
        if tel is not None:
            # the block's in-flight window (dispatch → losses landed) on
            # a virtual "device" track, so Perfetto shows device blocks
            # overlapping the host phases without breaking span nesting
            tel.tracer.record("block_inflight", int(block.t0 * 1e9),
                              int(t_wait1 * 1e9), cat="pipeline",
                              track="device", steps=len(block.sizes),
                              block=block.index)
        per_step = (time.perf_counter() - block.t0) / len(block.sizes)
        state = self.state
        scale = self._records_scale()
        ended = False
        t_replay0 = time.perf_counter()
        with self._tel_span("replay", "replay", steps=len(block.sizes),
                            block=block.index):
            for j, n_local in enumerate(block.sizes):
                n = n_local * scale
                state["neval"] += 1
                state["records_processed_this_epoch"] += n
                state["loss"] = float(losses[j])
                state["throughput"] = n / per_step
                # finite flags ride the psum'd global loss — every
                # process fetches the same reduced values
                # replicated-by: global-loss-reduction
                if finite is not None and not finite[j]:
                    self._on_nonfinite_step(j, losses)
                lr = block.lrs[j]
                self._log_train_iteration(lr)
                if self.train_summary is not None:
                    self.train_summary.add_train_step(
                        state["neval"], state["loss"], lr,
                        state["throughput"])
                    self._log_parameter_histograms(params)
                state["epoch_finished"] = \
                    state["records_processed_this_epoch"] >= self._epoch_size
                # the records counter advances by GLOBAL records, so
                # epoch rollover (shuffle + iterator reset) is uniform
                # replicated-by: lockstep-driver-counters
                if state["epoch_finished"]:
                    state["epoch"] += 1
                    state["records_processed_this_epoch"] = 0
                    self.dataset.shuffle()
                    self._stager.reset(self.dataset.data(train=True))
                self._run_validation(params, mstate)
                self._maybe_checkpoint(params, mstate, ostate)
                state["epoch_finished"] = False
                if self._fault_injector is not None \
                        and self._membership is not None:
                    # membership fault site (resize/host_loss/
                    # device_loss clauses, keyed by the same 0-based
                    # global iteration number as the batch kinds) —
                    # the signal lands here; the driver loop detects
                    # the epoch change at its next replay boundary
                    for clause in self._fault_injector \
                            .membership_events(state["neval"] - 1):
                        self._apply_membership_clause(clause)
                # end_when reads the same lockstep counters — training
                # stops on every process at the same iteration
                # replicated-by: lockstep-driver-counters
                if self.end_when(state):
                    ended = True
                    break
        if tel is not None:
            tel.stalls.record_block(block.stage_s, block.dispatch_s,
                                    t_wait1 - t_wait0,
                                    time.perf_counter() - t_replay0,
                                    first_compile=block.first_compile)
            tel.memory.observe()
            self._mirror_telemetry_scalars(tel)
        return ended

    def _mirror_telemetry_scalars(self, tel) -> None:
        """Mirror the driver gauges (pipeline-phase fractions, memory
        watermarks) into the TrainSummary event file, one scalar per
        gauge per replayed block — the telemetry view rides alongside
        Loss/Throughput in TensorBoard."""
        summary = self.train_summary
        add = getattr(summary, "add_scalar", None) if summary else None
        if add is None:
            return
        step = self.state["neval"]
        for name, val in tel.registry.gauges().items():
            add(f"Telemetry/{name}", float(val), step)

    # placement hooks — DistriOptimizer overrides these for sharded /
    # multi-host evaluation; the loop itself lives only here
    def _place_eval_input(self, x):
        return device_tree(x)

    def _place_eval_target(self, t):
        return device_tree(t)

    def _gather_eval_output(self, out):
        return out

    def evaluate_with(self, params, mstate) -> dict:
        """Forward the validation set through the model in eval mode."""
        if self._eval_fwd is None:
            model = self.model

            @jax.jit
            def fwd(params, mstate, x):
                out, _ = model.apply(params, mstate, x, training=False)
                return out

            self._eval_fwd = fwd

        acc: dict[str, ValidationResult] = {}
        for batch in self.validation_dataset.data(train=False):
            if not isinstance(batch, MiniBatch):
                raise TypeError("validation dataset must yield MiniBatch "
                                "(attach SampleToMiniBatch)")
            out = self._eval_fwd(params, mstate,
                                 self._place_eval_input(batch.input))
            out = self._gather_eval_output(out)
            tgt = self._place_eval_target(batch.target)
            for m in self.validation_methods:
                r = m(out, tgt)
                acc[m.name] = acc[m.name] + r if m.name in acc else r
        if not acc:
            raise ValueError(
                "validation dataset yielded no batches — its size is smaller "
                "than the batch size and SampleToMiniBatch dropped the "
                "remainder; use SampleToMiniBatch(n, drop_remainder=False) "
                "for validation or shrink the batch")
        return acc


class LocalOptimizer(Optimizer):
    """Single-host training loop (reference ``LocalOptimizer.scala:45``).

    The reference clones the model per core and sums gradients across
    thread replicas; under XLA one jit'd step-block uses the whole chip,
    so the loop is: stage next block → dispatch fused (loss, grad,
    update) block → replay triggers (see Optimizer._train_driver).
    """

    def optimize(self) -> Module:
        attempts = 0
        while True:
            try:
                return self._optimize_impl()
            except NonFiniteStepError as e:
                # numeric_guard="rollback": automatic loss-spike
                # recovery — restore the latest VALID snapshot (torn/
                # corrupt ones are skipped, never loaded) and re-run,
                # bounded by failure_retry_times.  "abort" (and an
                # exhausted budget) surfaces to the caller at the exact
                # failing iteration.
                attempts += 1
                from bigdl_tpu.utils.config import get_config
                self._rollback_nonfinite(
                    e, attempts, get_config().failure_retry_times)

    def _optimize_impl(self) -> Module:
        rng = jax.random.PRNGKey(self.seed)
        rng, init_rng = jax.random.split(rng)
        if self.model._params is not None:
            # copy: the block fn donates its inputs, and these arrays are
            # owned by the caller's model — donation would delete them,
            # corrupting the model on a failed/interrupted run
            params = jax.tree_util.tree_map(jnp.array, self.model._params)
            mstate = jax.tree_util.tree_map(jnp.array, self.model._state)
        else:
            params, mstate = self.model.init(init_rng)
        self._validate_resume_schema(params)
        if self._resume_opt_state is not None:
            ostate = self._resume_opt_state
            self._resume_opt_state = None
        else:
            ostate = self.optim_method.init_state(params)

        grad_fn = self._loss_and_grad_fn()
        logger.info("LocalOptimizer: %d samples/epoch, device=%s",
                    self.dataset.size(), jax.devices()[0])
        params, mstate, ostate = self._train_driver(params, mstate, ostate,
                                                    grad_fn, rng)
        # what a model's own counters say of the run (a model may define
        # ``state_warnings(state) -> list of str``: an expert layer's
        # dropped assignments), read once, after the last step
        for said in getattr(self.model, "state_warnings",
                            lambda state: [])(mstate):
            logger.warning("%s", said)

        # write trained weights back into the user's model object
        # (reference: final getModel copy, DistriOptimizer.scala:1063)
        self.model._params = params
        self.model._state = mstate
        self._final_opt_state = ostate
        return self.model
