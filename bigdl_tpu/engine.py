"""Engine — runtime/topology bookkeeping.

TPU-native analog of the reference's ``DL/utils/Engine.scala`` (553 LoC):
there, ``Engine.init`` parses Spark conf, sizes thread pools and records
node/core counts; every layer then calls ``Engine.default.invokeAndWait``
for intra-node parallelism.

On TPU none of that exists: intra-chip parallelism is XLA's job and
inter-chip parallelism is a ``jax.sharding.Mesh``.  What remains of the
Engine's role is topology bookkeeping — how many devices/hosts there are,
which mesh the optimizers should shard over — plus the ``bigdl.*``-style
config surface (reference: ``Engine.scala:45-47,190-215``), centralized
here as documented attributes instead of scattered system properties.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from bigdl_tpu.utils.config import get_config


# the checkout root (the directory holding the ``bigdl_tpu`` package)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _default_retry_times() -> int:
    return get_config().failure_retry_times


@dataclass
class _EngineState:
    initialized: bool = False
    mesh: Optional[Mesh] = None
    seed: int = 1
    # reference knob: bigdl.failure.retryTimes (DistriOptimizer retry
    # loop); default flows from the unified typed config
    # (utils/config.Config.failure_retry_times, env BIGDL_TPU_*)
    failure_retry_times: int = field(default_factory=_default_retry_times)
    # K-step dispatch fusion for the training driver loop.  None =
    # never set at the Engine level: steps_per_dispatch() then reads
    # the Config field (the one rule: utils/config.py);
    # Engine.set_steps_per_dispatch pins an explicit process-wide value
    steps_per_dispatch: Optional[int] = None
    # custom-kernel selection (ops/pallas_*.py): "auto" | "pallas" |
    # "xla"; None = unset, the Config field answers
    kernel_impl: Optional[str] = None
    # whether Engine.set_xla_async_collectives has armed the XLA
    # latency-hiding scheduler flags (None = never touched)
    xla_async_collectives: Optional[bool] = None


class Engine:
    """Process-wide runtime state.  ``Engine.init()`` is idempotent.

    Reference parity: ``Engine.init`` (``DL/utils/Engine.scala:105-118``),
    ``Engine.nodeNumber()/coreNumber()`` → :meth:`node_number` /
    :meth:`core_number` report JAX process/device counts instead of Spark
    executors/cores.
    """

    _state = _EngineState()

    # -- lifecycle ---------------------------------------------------------
    @classmethod
    def init(cls, seed: int = 1) -> None:
        cls._state.initialized = True
        cls._state.seed = seed

    @classmethod
    def is_initialized(cls) -> bool:
        return cls._state.initialized

    @staticmethod
    def enable_compile_cache() -> str:
        """Turn on JAX's persistent compilation cache for this process
        and return its directory — the ONE place the repo decides where
        compiled programs are kept (entry scripts call this before
        their first jit; the test suite never does).

        Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
        it and nothing else is set here, so the cache can be placed
        from outside.  Unset, the cache lives at the fixed
        ``<checkout>/.jax_cache``: the path is part of the cache key's
        world, so a directory that moves (tempfile, pid, timestamp)
        never hits.  The minimum compile time is dropped to zero (the
        entry-size floor already is) so every program is stored — the
        minutes-long ResNet-50 step and the sub-second serving bucket
        executables alike."""
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(_CHECKOUT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        return jax.config.jax_compilation_cache_dir

    @classmethod
    def reset(cls) -> None:
        cls._state = _EngineState()

    # -- topology ----------------------------------------------------------
    @classmethod
    def node_number(cls) -> int:
        """Number of hosts (reference: Spark executor count)."""
        return jax.process_count()

    @classmethod
    def core_number(cls) -> int:
        """Devices per host (reference: cores per executor)."""
        return jax.local_device_count()

    @classmethod
    def device_count(cls) -> int:
        return jax.device_count()

    # -- mesh --------------------------------------------------------------
    @classmethod
    def set_mesh(cls, mesh: Mesh) -> None:
        cls._state.mesh = mesh

    @classmethod
    def get_mesh(cls) -> Mesh:
        """The mesh distributed optimizers shard over.

        Defaults to a 1-D data-parallel mesh over all devices — the direct
        analog of the reference's one-replica-per-core data parallelism
        (``DistriOptimizer.scala:136-139``), minus the per-core replication
        (the batch is sharded over devices instead).
        """
        if cls._state.mesh is None:
            devs = np.array(jax.devices())
            cls._state.mesh = Mesh(devs, axis_names=("data",))
        return cls._state.mesh

    # -- config ------------------------------------------------------------
    @classmethod
    def seed(cls) -> int:
        return cls._state.seed

    @classmethod
    def steps_per_dispatch(cls) -> int:
        """How many train steps the driver fuses into one jit dispatch:
        :meth:`set_steps_per_dispatch` where it was called, else
        ``Config.steps_per_dispatch`` (the one rule: utils/config.py)."""
        if cls._state.steps_per_dispatch is not None:
            return max(1, int(cls._state.steps_per_dispatch))
        return max(1, int(get_config().steps_per_dispatch))

    @classmethod
    def set_steps_per_dispatch(cls, k: int) -> None:
        if int(k) < 1:
            raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
        cls._state.steps_per_dispatch = int(k)

    @classmethod
    def kernel_impl(cls) -> str:
        """Process-wide custom-kernel choice (``auto|pallas|xla``) the
        pallas-backed layers resolve when built without an explicit
        ``impl=``; see ``Config.kernel_impl`` for the semantics and
        ``ops.resolve_kernel_impl`` for the auto rule.
        :meth:`set_kernel_impl` where it was called, else the Config
        field."""
        if cls._state.kernel_impl is not None:
            return cls._state.kernel_impl
        impl = get_config().kernel_impl
        if impl not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"kernel_impl must be auto|pallas|xla, got {impl!r}")
        return impl

    @classmethod
    def set_kernel_impl(cls, impl: str) -> None:
        if impl not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"kernel_impl must be auto|pallas|xla, got {impl!r}")
        cls._state.kernel_impl = impl

    # -- serving -----------------------------------------------------------
    @classmethod
    def serving_defaults(cls) -> dict:
        """Process-wide defaults for :class:`bigdl_tpu.serving.
        InferenceService` knobs (config ``serving_*`` fields /
        ``BIGDL_TPU_SERVING_*`` env); per-service constructor args
        override.  ``row_buckets`` is the parsed-ready bucket spec
        string (``serving_row_buckets``; "" = power-of-two auto)."""
        cfg = get_config()
        return {
            "max_batch_size": cfg.serving_max_batch_size,
            "batch_timeout_ms": cfg.serving_batch_timeout_ms,
            "queue_capacity": cfg.serving_queue_capacity,
            "row_buckets": cfg.serving_row_buckets,
            # resilience: the per-request deadline a ReplicaSet stamps
            # on submissions (0 = none)
            "deadline_ms": cfg.serving_deadline_ms,
        }

    # -- XLA collective scheduling ----------------------------------------
    # The grad_sync design (parallel/grad_sync.py) leans on XLA's
    # latency-hiding scheduler to overlap per-bucket reduce-scatter /
    # all-gather with backward compute.  On TPU that scheduling is
    # governed by XLA flags that must be set BEFORE the backend
    # initializes; this is the one documented place to flip them.
    _ASYNC_COLLECTIVE_FLAGS = (
        "--xla_tpu_enable_latency_hiding_scheduler",
        "--xla_tpu_enable_async_collective_fusion",
    )

    @classmethod
    def set_xla_async_collectives(cls, enable: bool = True) -> None:
        """Arm (or disarm) XLA's async-collective / latency-hiding
        scheduler flags via ``XLA_FLAGS``.  Call BEFORE the first jax
        computation — XLA reads the env once at backend init, so a
        later call only reaches processes started afterwards.

        No acceptance probe: these are TPU-build flags, and a jaxlib
        whose backend does not know them aborts the process at backend
        init ("Unknown flags in XLA_FLAGS") — loudly, at start, which
        is the wanted failure.  (Probing in a child process would need
        the child to take the chip this process is about to use.)"""
        cls._state.xla_async_collectives = bool(enable)
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if f.split("=")[0] not in cls._ASYNC_COLLECTIVE_FLAGS]
        val = "true" if enable else "false"
        flags += [f"{f}={val}" for f in cls._ASYNC_COLLECTIVE_FLAGS]
        os.environ["XLA_FLAGS"] = " ".join(flags)

    @classmethod
    def xla_async_collectives(cls) -> Optional[bool]:
        """Last value passed to :meth:`set_xla_async_collectives`
        (None = untouched defaults)."""
        return cls._state.xla_async_collectives
