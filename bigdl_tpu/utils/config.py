"""Unified typed configuration.

Reference: the ``bigdl.*`` Java system properties scattered across
``Engine.scala:45-47,190-235`` / ``AllReduceParameter.scala:36-47``
(``bigdl.engineType``, ``bigdl.coreNumber``, ``bigdl.failure.retryTimes``,
``bigdl.check.singleton``, …) + the required ``spark-bigdl.conf`` overlay
+ per-example scopt parsers.  SURVEY §5 flags the lack of one typed
config object as a thing for the new build to centralize — this is it.

THE ONE RULE for what value a knob has (first that answers wins):

    per-object setter or constructor argument
      > ``Engine.set_*`` (process-wide)
      > ``configure(**kw)``
      > ``BIGDL_TPU_<FIELD>`` environment variable
      > dataclass default

The last three are this module: ``get_config()`` builds the one
``Config`` from the defaults and the environment, and ``configure()``
overwrites its fields.  The first two live where the object is: a call
site holds ``None`` for "never set" and then reads ``get_config()`` (or
the ``Engine`` accessor that does).  Nothing else decides a value.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

_ENV_PREFIX = "BIGDL_TPU_"


@dataclasses.dataclass
class Config:
    # failure handling (reference bigdl.failure.retryTimes, default 5)
    failure_retry_times: int = 5
    # data pipeline
    prefetch_batches: int = 2          # MTSampleToMiniBatch default queue
    loader_workers: int = 4            # per-host preprocessing threads
    # driver loop: K consecutive train steps fused into ONE jit dispatch
    # (lax.scan over stacked microbatches).  1 = classic step-per-dispatch;
    # raise for dispatch-bound workloads (small-step LSTMs, sparse recs).
    # Blocks are auto-flushed at epoch/trigger boundaries, so semantics
    # are K-invariant; see README "stepping & input pipeline".
    steps_per_dispatch: int = 1
    # gradient sync (parallel/grad_sync.py — the AllReduceParameter
    # analog): grads are flattened into buckets of at most
    # grad_bucket_bytes (f32 accounting) so per-bucket reduce-scatters
    # overlap backward compute, and the wire dtype controls the
    # on-the-wire compression (reference FP16CompressedTensor; BENCH
    # r05 measured collective_overhead_fraction=0.32 at 8 chips, so
    # compression matters even over ICI).  "f32" | "bf16" | "f16";
    # the bf16 wire downcasts with unbiased stochastic rounding; f16
    # uses round-to-nearest (64x finer ulp) with SATURATION at ±65504
    # — gradient spikes clamp instead of going inf on the wire (see
    # utils/precision.stochastic_round, parallel/grad_sync.wire_cast).
    # The optimizer update always accumulates in f32 master slices.
    grad_bucket_bytes: int = 4 << 20
    grad_wire_dtype: str = "f32"
    # checkpointing (bigdl_tpu/checkpoint — async fault-tolerant
    # snapshots): retention keeps the newest checkpoint_keep_last
    # snapshots plus (with checkpoint_keep_every=N) every N-th step
    # forever; checkpoint_async=True commits snapshots on a bounded
    # background writer thread so the driver pays only the device→host
    # capture (checkpoint/stall_fraction gauge proves it) — False
    # restores the synchronous inline write (debugging / tiny runs).
    checkpoint_keep_last: int = 5
    checkpoint_keep_every: int = 0
    checkpoint_async: bool = True
    # serving (bigdl_tpu/serving — dynamic-batching inference engine):
    # a coalesced batch dispatches when it reaches serving_max_batch_size
    # rows or serving_batch_timeout_ms after its first request; the
    # request queue holds at most serving_queue_capacity requests before
    # submit() raises ServiceOverloaded (explicit backpressure).  The
    # timeout is the latency/occupancy dial: ~1-5 ms suits interactive
    # traffic, tens of ms squeezes occupancy out of sparse traffic, 0
    # is adaptive mode (dispatch whatever is already queued — the
    # previous dispatch's latency is the coalescing window; the
    # PredictionService shim runs this way).
    serving_max_batch_size: int = 32
    serving_batch_timeout_ms: float = 2.0
    serving_queue_capacity: int = 256
    # resilience (bigdl_tpu/resilience — designed-in failure handling):
    # serving_deadline_ms is the default per-request deadline a
    # ReplicaSet stamps on submissions (0 = none; the deadline travels
    # with the request — expired work is refused before the device
    # call, and the supervisor fails work stuck on a dead replica so
    # the router can retry it elsewhere).  numeric_guard is the
    # training driver's non-finite loss/grad policy: "off" (default —
    # provably inert) | "skip" (jnp.where-gate the update on device,
    # count, continue) | "rollback" (restore the latest VALID
    # checkpoint, bounded by failure_retry_times) | "abort" (fail
    # loudly at the exact iteration).  fault_plan names a deterministic
    # fault-injection plan (grammar in resilience/faults.py; "" = no
    # injector object even exists — the bitwise-inert state) seeded by
    # fault_seed, so every degradation path is gated by a test instead
    # of hand-checked during incidents.
    serving_deadline_ms: float = 0.0
    numeric_guard: str = "off"
    fault_plan: str = ""
    fault_seed: int = 0
    # custom-kernel selection (bigdl_tpu/ops/pallas_*.py — the fused
    # LSTM cell and COO embedding-bag):  "xla" = always the baseline
    # lowering; "pallas" = fused kernel wherever its measured
    # supported() gate passes (silent XLA fallback otherwise; interpret
    # mode off-TPU); "auto" = pallas-if-supported on a TPU backend, xla
    # elsewhere (interpret-mode kernels are correctness-emulation, not
    # a speedup, so auto never engages them on CPU hosts).  Resolved
    # through Engine.kernel_impl().  Env: BIGDL_TPU_KERNEL_IMPL.
    # Per-layer ``impl=`` constructor args win.
    kernel_impl: str = "auto"
    # int8 quantized inference (nn/quantized.py over
    # ops/pallas_int8_gemm.py).  int8_activation_mode is the default
    # per-layer mode quantize(model) stamps on converted layers:
    # "weight_only" (int8 weights, f32/bf16 activations, f32 MXU
    # accumulation — no activation quantization error, the serving
    # default) or "dynamic" (BigQuant-style on-the-fly int8
    # activations, int32 accumulate).  int8_block_rows is the GEMM
    # row-block size, 0 = auto (<=128 whole-batch, else 128-row
    # blocks).  Env: BIGDL_TPU_INT8_ACTIVATION_MODE /
    # BIGDL_TPU_INT8_BLOCK_ROWS.
    int8_activation_mode: str = "weight_only"
    int8_block_rows: int = 0
    # activation-memory policy default (Optimizer.set_activation_memory
    # overrides per run): "none" | "dots" | "full" | "bf16" |
    # "bf16+dots" | "bf16+full" — remat / bf16 activation storage for
    # HBM-bound workloads (see optim/optimizer.py for the semantics).
    # Env: BIGDL_TPU_ACTIVATION_MEMORY.
    activation_memory: str = "none"
    # serving row-bucket set: "" or "pow2" = power-of-two buckets up to
    # serving_max_batch_size (serving.row_buckets — the default);
    # "top" = one bucket at max_batch_size (max executable sharing, max
    # padding); "8,16,32" = explicit ascending list whose top must be
    # >= serving_max_batch_size.  Parsed by serving.parse_row_buckets.
    serving_row_buckets: str = ""
    # numerics
    compute_dtype: str = "float32"     # "bfloat16" flips matmul precision
    matmul_precision: str = "default"  # jax "default"|"high"|"highest"
    # NaN sanitizer (SURVEY §5: lean on jax.debug_nans instead of the
    # reference's per-layer checks): opt in via BIGDL_TPU_DEBUG_NANS=1
    # or configure(debug_nans=True), then call apply_debug_config()
    debug_nans: bool = False
    # logging / observability
    log_every_n_iterations: int = 1
    summary_flush_secs: float = 10.0
    # telemetry (bigdl_tpu/telemetry): step-timeline tracer + metric
    # registry + runtime watchdogs wired through the training driver.
    # Provably inert — enabling adds no dispatch and no host sync; the
    # loss sequence is bitwise identical (tests/test_telemetry.py).
    # BIGDL_TPU_TELEMETRY=1 is the short env alias for
    # BIGDL_TPU_TELEMETRY_ENABLED=1.  telemetry_trace_path: write the
    # Chrome-trace JSON there when training ends ("" = keep in memory;
    # summarize with `python -m tools.trace_report <path>`).
    telemetry_enabled: bool = False
    telemetry_trace_path: str = ""
    telemetry_trace_capacity: int = 200_000  # retained spans, then drop+count
    # admin plane (telemetry/admin.py): a stdlib http.server thread
    # serving /metrics (Prometheus text), /healthz (JSON), /trace
    # (Chrome-trace dump), /flight (flight-recorder ring) and
    # /profile?seconds=N (on-demand jax.profiler capture).  0 (default)
    # = OFF — no socket, no thread, provably inert.  Binds 127.0.0.1
    # only (no auth on this surface — see README "Admin plane").
    # Env: BIGDL_TPU_ADMIN_PORT.
    admin_port: int = 0
    # request-scoped tracing (telemetry/context.py): mint a
    # RequestContext (trace_id, tenant, hop history, Chrome flow
    # events) per serving submit and propagate it through coalescing,
    # dispatch and ReplicaSet failover.  Off (default) = no context
    # object is ever allocated — the serving path is byte-identical.
    # Env: BIGDL_TPU_REQUEST_TRACING.
    request_tracing: bool = False
    # flight recorder (telemetry/flight.py): append-and-flush JSONL
    # stream of structured events (health transitions, breaker trips,
    # failovers, sheds, rollbacks, recompiles, checkpoint commits,
    # preemption) with trace_id correlation — survives SIGKILL, joined
    # with a trace by `python -m tools.obs_report`.  "" (default) =
    # OFF — nothing allocated, nothing opened.  Env:
    # BIGDL_TPU_FLIGHT_RECORDER_PATH / _CAPACITY.
    flight_recorder_path: str = ""
    flight_recorder_capacity: int = 4096  # in-memory ring bound
    # wire frontend (frontend/server.py): the port
    # FrontendServer(port=None) binds the HTTP serving endpoint on.
    # 0 (default) = the frontend refuses config-driven construction —
    # unlike the admin plane nothing auto-starts either way; the wire
    # surface only exists when a FrontendServer is explicitly built.
    # Binds 127.0.0.1 only (X-Tenant is a tag, not a credential).
    # Env: BIGDL_TPU_FRONTEND_PORT.
    frontend_port: int = 0
    # wire-frontend auth (frontend/server.py): when set, every request
    # must carry `Authorization: Bearer <token>` or is refused 401 —
    # and a FrontendServer REFUSES to bind a non-loopback host unless
    # a token is configured (X-Tenant stays a QoS tag, never a
    # credential).  "" (default) keeps the historical loopback-open
    # behavior.  Env: BIGDL_TPU_FRONTEND_AUTH_TOKEN.
    frontend_auth_token: str = ""
    # wire-frontend connection core (frontend/server.py +
    # frontend/eventloop.py): "eventloop" (default) serves every
    # connection from a small set of selector loop threads with
    # incremental HTTP/1.1 parsing and callback-driven writes — no
    # thread per connection; "threaded" keeps the PR-14
    # thread-per-connection stdlib core.  Both speak the identical
    # wire surface (one shared test suite).  Env: BIGDL_TPU_FRONTEND_CORE.
    frontend_core: str = "eventloop"
    # event-loop shard count: number of loop threads, each binding its
    # own SO_REUSEPORT listener on the same port so the kernel spreads
    # accepts (multi-core fan-in).  Platforms without SO_REUSEPORT fall
    # back to one shared listener round-robined across the loops.
    # Env: BIGDL_TPU_FRONTEND_SHARDS.
    frontend_shards: int = 1
    # hard cap on concurrently-open wire connections (both cores):
    # past it, fresh accepts are refused with a bare close before any
    # parser/thread exists — counted frontend/conns_refused.  0 =
    # uncapped.  Env: BIGDL_TPU_FRONTEND_MAX_CONNECTIONS.
    frontend_max_connections: int = 10000
    # idle keep-alive reap timeout (seconds): connections with no
    # in-flight exchange and no traffic for this long are closed
    # (frontend/conns_reaped), so idle floods cannot starve active
    # clients of fds.  0 = never reap.  Env:
    # BIGDL_TPU_FRONTEND_IDLE_TIMEOUT_S.
    frontend_idle_timeout_s: float = 120.0
    # pin each event-loop shard thread to one CPU
    # (os.sched_setaffinity, loop i → available cpu i mod count) so
    # shards stop migrating across cores under load (cache/IRQ
    # locality).  Silently inert on platforms without sched_setaffinity
    # (macOS, Windows).  Env: BIGDL_TPU_FRONTEND_PIN_CPUS.
    frontend_pin_cpus: bool = False
    # lockdep (utils/lockdep.py): TSan-lite lock-order sanitizer for
    # the threaded host plane.  False (default) = provably inert — no
    # wrapper object is ever allocated, threading.Lock/RLock stay the
    # stdlib factories (the FaultInjector empty-plan discipline).
    # True (or BIGDL_TPU_LOCKDEP=1) wraps lock CONSTRUCTION so every
    # tier-1 run doubles as a deadlock hunt: per-thread held-lock
    # stacks accrete a global acquisition-order graph and a cycle is
    # reported AT ACQUIRE TIME with both conflicting stacks.
    # lockdep_hold_ms additionally records holds longer than the
    # threshold (blocking-under-lock, GL206's runtime twin); 0
    # disables the wall-clock check.
    lockdep: bool = False
    lockdep_hold_ms: float = 200.0
    # spmdcheck (utils/spmdcheck.py): collective-schedule sanitizer for
    # multi-host SPMD divergence — the runtime twin of graftlint
    # GL401-GL404.  False (default) = provably inert: the driver's
    # note sites read one module global and return; nothing is
    # allocated.  True (or BIGDL_TPU_SPMDCHECK=1) records the sequence
    # of (op kind, axis, payload treedef/dtype) each emulated process
    # issues and the first cross-process mismatch is reported with
    # both schedules + both stacks.
    spmdcheck: bool = False
    # mesh defaults (dryrun/tests override explicitly)
    mesh_data: int = -1
    mesh_model: int = 1
    mesh_seq: int = 1
    mesh_pipe: int = 1

    @staticmethod
    def _coerce(value: str, typ):
        if typ is bool:
            return value.lower() in ("1", "true", "yes", "on")
        return typ(value)

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls()
        for f in dataclasses.fields(cls):
            env = _ENV_PREFIX + f.name.upper()
            if env in os.environ:
                setattr(cfg, f.name,
                        cls._coerce(os.environ[env], type(getattr(cfg,
                                                                  f.name))))
        # short alias: BIGDL_TPU_TELEMETRY=1 ⇔ BIGDL_TPU_TELEMETRY_ENABLED=1
        # (the explicit long form wins when both are set)
        alias = _ENV_PREFIX + "TELEMETRY"
        if alias in os.environ and \
                _ENV_PREFIX + "TELEMETRY_ENABLED" not in os.environ:
            cfg.telemetry_enabled = cls._coerce(os.environ[alias], bool)
        return cfg


_config: Optional[Config] = None


def get_config() -> Config:
    global _config
    if _config is None:
        _config = Config.from_env()
        if _config.debug_nans:
            # BIGDL_TPU_DEBUG_NANS=1 alone must be enough: push the
            # toggle into jax as soon as the config is first read
            apply_debug_config(_config)
    return _config


def configure(**kw) -> Config:
    """Override config fields programmatically (above the environment,
    below ``Engine.set_*`` and per-object setters: the module docstring)."""
    cfg = get_config()
    for k, v in kw.items():
        if k.startswith("_") or not hasattr(cfg, k):
            names = [f.name for f in dataclasses.fields(Config)]
            raise AttributeError(
                f"unknown config field {k!r}; fields: {names}")
        setattr(cfg, k, v)
    if "debug_nans" in kw:
        apply_debug_config(cfg)
    return cfg


def reset_config() -> None:
    """Drop overrides; next get_config() re-reads the environment."""
    global _config
    _config = None


def apply_debug_config(cfg: Optional[Config] = None) -> None:
    """Push debug toggles into the jax runtime (the ``debug_nans``
    sanitizer makes every jit'd computation fail LOUDLY at the first
    NaN instead of training garbage — the reference's NaN checks are
    scattered per-layer asserts)."""
    import jax
    cfg = cfg or get_config()
    jax.config.update("jax_debug_nans", bool(cfg.debug_nans))
