"""Continuous-batching autoregressive decode (ROADMAP item 1, part b).

The batch-inference engine (``serving/service.py``) coalesces fixed-shape
requests into one dispatch — the right shape for encoder traffic, the
WRONG shape for autoregressive decode, where padding a request batch to
its slowest member holds a 4-token reply hostage to a 512-token one.
This module schedules at **iteration (step) granularity** instead — the
Orca/vLLM discipline:

- a **slotted KV cache** sized to a declared budget: k/v each
  ``(L, slots, H, max_seq_len, Dh)`` device arrays
  (``models/transformer.py`` decode carry); a sequence owns one slot
  from admission to EOS/max-tokens/deadline, then the slot is reclaimed
  the same step and the next queued sequence takes it;
- **prefill buckets** extending the PR-5 AOT ladder: prompts are padded
  to a sequence-length bucket (``parse_row_buckets`` — the grammar's
  ``pow2@<floor>`` form exists for exactly this) and every bucket's
  prefill + cache-splice executables are AOT-compiled at construction,
  so steady-state admission never traces;
- one **decode-step executable** over the full slot batch: every step
  advances ALL active sequences one token; new sequences are admitted
  into the running batch BETWEEN steps (never blocking on in-flight
  sequences finishing), which the accounting exposes as
  ``admit_step``/``finish_step`` on every :class:`DecodeResult`;
- **deadlines and per-tenant QoS ride the existing request path**: each
  queued sequence is a :class:`~bigdl_tpu.serving.batcher._Request`
  (deadline + RequestContext + future), admission under pressure ranks
  by the same ``priority_fn`` contract the batcher uses (frontend
  :class:`~bigdl_tpu.frontend.QosAdmission` plugs in unchanged), and an
  expired sequence — queued or mid-decode — settles
  :class:`DeadlineExceeded`;
- **token streaming**: ``submit(..., on_token=fn)`` delivers each token
  as generated (the frontend's chunked-ndjson generate route rides
  this).

Threading: ONE scheduler thread owns the device caches and all slot
bookkeeping (single-owner, no lock needed there); the cross-thread
surface (queue, lifecycle flags, active count) is guarded by ``_cond``'s
lock.  Metrics land on a :class:`~bigdl_tpu.serving.ServingMetrics`
(dispatch accounting reads as step occupancy: ``record_dispatch(active,
slots)`` per step, so ``mean_batch_occupancy`` is the continuous-batching
win).

Measured from inside (PR 37).  With a :class:`~bigdl_tpu.telemetry.Tracer`
(``tracer=``, or one of the service's own under
``Config.telemetry_enabled`` / ``BIGDL_TPU_TELEMETRY=1``) four top-level
spans tile every pass of the scheduler's loop — ``idle``, ``schedule``,
one ``admit`` a prefill, one ``step`` — and their children name what the
host does inside (``prefill_launch``, ``splice_launch``, ``first_fetch``,
``emit``; ``step_h2d``, ``dispatch``, ``device_wait``, ``step_fetch``,
``emit``); each request leaves a ``queue_wait`` and a ``sequence`` span
on tracks of their own.  Which span lies in which category:
:data:`SPAN_CATS`; which categories are top-level:
``telemetry.tracer.DECODE_PHASE_CATS``.  Every span on the scheduler's
thread is mirrored into a running profiler capture
(``benchmarks/host_spans.py`` finds the thread by its ``dispatch`` span),
and as it closes its time goes into a histogram of the service's
registry (``decode/span_ms/<name>``), which is what ``stats()`` reads:
exact sums and counts since the start and a median over the newest 4,096
spans, however long the service has run.  The tracer's own buffer
(``/trace``, ``Tracer.dump``) holds the FIRST ``capacity`` events and
then drops and counts (``trace_dropped_events`` in ``stats()``;
``tracer.clear()`` arms it again); the mirror and the histograms go on.
With no tracer the loop makes no span: a call site then costs one call
that returns the shared no-op.  The counters and histograms of the
work itself (queue wait, step time, key/value positions used against
reserved, prefill padding, the bytes an admission fetches, expiries by
place) are always on.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, List, Optional

import numpy as np

from bigdl_tpu.serving.batcher import (DeadlineExceeded, RequestSpecError,
                                       ServiceClosed, ServiceOverloaded,
                                       _Request, settle_future)
from bigdl_tpu.serving.metrics import ServingMetrics
from bigdl_tpu.serving.service import parse_row_buckets
from bigdl_tpu.telemetry.tracer import DECODE_PHASE_CATS, NULL_SPAN

logger = logging.getLogger("bigdl_tpu.serving")

# every span the scheduler's thread makes, and its category
SPAN_CATS = {
    "idle": "decode_idle", "schedule": "decode_schedule",
    "admit": "decode_admit", "step": "decode_step",
    "prefill_launch": "decode_launch", "splice_launch": "decode_launch",
    "dispatch": "decode_launch",
    "first_fetch": "decode_fetch", "step_fetch": "decode_fetch",
    "step_h2d": "decode_h2d", "device_wait": "decode_device_wait",
    "emit": "decode_emit",
}


class _TimedSpan:
    """A tracer's span whose time, as it closes, also goes into the
    service's histogram of that span name (the running state ``stats()``
    reads).  ``with`` hands back the tracer's own span (``set``,
    ``dur_ns``)."""

    __slots__ = ("_sp", "_hist", "_svc")

    def __init__(self, sp, hist, svc):
        self._sp = sp
        self._hist = hist
        self._svc = svc     # of a top-level span; ``None`` of a child

    def __enter__(self):
        return self._sp.__enter__()

    def __exit__(self, *exc):
        self._sp.__exit__(*exc)
        self._hist.observe(self._sp.dur_ns / 1e6)
        if self._svc is not None:
            self._svc._loop_t1_ns = time.perf_counter_ns()
        return False


class DecodeResult:
    """What a decode future resolves to.

    - ``tokens``: np.int32 array of generated tokens (includes the EOS
      token when ``finish_reason == "eos"``);
    - ``finish_reason``: ``"eos"`` | ``"length"`` (max-new-tokens or
      context cap);
    - ``admit_step`` / ``finish_step``: the scheduler's global step
      counter at admission / completion — the dispatch accounting that
      PROVES continuous batching (request B with ``A.admit_step <
      B.admit_step < A.finish_step`` joined A's running batch);
    - ``slot``: the KV-cache slot the sequence occupied (slot-reuse
      audits);
    - ``prompt_len`` / ``prefill_bucket``: request size and the AOT
      bucket its prefill padded into.
    """

    __slots__ = ("tokens", "finish_reason", "admit_step", "finish_step",
                 "slot", "prompt_len", "prefill_bucket")

    def __init__(self, tokens, finish_reason, admit_step, finish_step,
                 slot, prompt_len, prefill_bucket):
        self.tokens = tokens
        self.finish_reason = finish_reason
        self.admit_step = admit_step
        self.finish_step = finish_step
        self.slot = slot
        self.prompt_len = prompt_len
        self.prefill_bucket = prefill_bucket


class _Pending:
    """A queued decode request: the generic :class:`_Request` (future /
    deadline / ctx / t_enqueue — the existing request path) plus the
    decode-only fields that don't fit its __slots__.  ``rid`` names the
    request in every span it leaves (the caller's ``ctx.trace_id``, else
    the service's count of submissions); ``t_submit_ns`` is stamped only
    when the service holds a tracer."""

    __slots__ = ("req", "max_new", "on_token", "rid", "t_submit_ns")

    def __init__(self, req: _Request, max_new: int, on_token):
        self.req = req
        self.max_new = max_new
        self.on_token = on_token
        self.rid = None
        self.t_submit_ns = 0


class _Sequence:
    """One active slot: scheduler-thread-owned bookkeeping."""

    __slots__ = ("pend", "prompt_len", "bucket", "generated",
                 "admit_step", "slot", "t_admit_ns")

    def __init__(self, pend: _Pending, prompt_len: int, bucket: int,
                 admit_step: int, slot: int, t_admit_ns: int = 0):
        self.pend = pend
        self.prompt_len = prompt_len
        self.bucket = bucket
        self.generated: List[int] = []
        self.admit_step = admit_step
        self.slot = slot
        self.t_admit_ns = t_admit_ns  # start of its ``sequence`` span


class DecodeService:
    """Continuous-batching decode engine for one ``transformer_lm``.

    Parameters:

    - ``slots``: concurrent-sequence capacity (the decode batch width).
    - ``max_seq_len``: per-sequence context cap (prompt + generated);
      clamped to the model's positional-embedding table.
    - ``kv_budget_mb``: declared KV-cache budget.  The cache is sized
      up front (two ``(L, slots, H, max_seq_len, Dh)`` f32 arrays); if
      that exceeds the budget, ``slots`` is CUT to what fits (raising
      if not even one slot fits) — the budget is a hard cap, not a
      hint.
    - ``prefill_buckets``: sequence-length bucket spec
      (:func:`~bigdl_tpu.serving.service.parse_row_buckets` grammar
      over ``max_prompt_len``; default ``"pow2@8"``).
    - ``eos_id``: token id that finishes a sequence (None = length-only
      stopping); ``default_max_new_tokens`` caps generation when the
      caller doesn't.
    - ``deadline_ms``: default per-request deadline (0/None = none).
    - ``mesh``: optional :class:`~jax.sharding.Mesh` — params are
      placed with the model's declared ``param_specs`` shardings
      (the ``ShardedReplicaSet`` discipline), making this a
      sharded-decode backend.
    - ``priority_fn``: the batcher's QoS contract — maps a queued
      ``_Request`` to an int rank (lower admits first), engaged only
      under pressure (more queued than free slots).
    - ``tracer``: a :class:`~bigdl_tpu.telemetry.Tracer` for the
      scheduler's spans (module docstring); ``None`` makes one when
      ``Config.telemetry_enabled`` is set and otherwise holds none, and
      the loop then makes no span.  ``stats()["decode"]`` then holds
      ``spans`` (per span name: category, seconds, count, median),
      ``host_step_ms`` (a step less its ``device_wait``) and
      ``loop_unspanned_share``, all from running sums.

    Greedy (argmax) decoding — deterministic, so serving output equals
    the full-context reference run token-for-token (the acceptance
    gate).
    """

    # duck-type marker the frontend's generate route checks — a backend
    # without it answers 400 (predict backends don't decode)
    is_decode_backend = True

    def __init__(self, model, params=None, state=None, *,
                 slots: int = 4, max_seq_len: int = 256,
                 max_prompt_len: Optional[int] = None,
                 default_max_new_tokens: int = 32,
                 eos_id: Optional[int] = None,
                 prefill_buckets: Optional[str] = None,
                 kv_budget_mb: Optional[float] = None,
                 queue_capacity: int = 64,
                 deadline_ms: Optional[float] = None,
                 name: str = "decode", mesh=None,
                 registry=None, priority_fn=None, tracer=None,
                 start: bool = True):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.models.transformer import (kv_cache_spec, lm_layout,
                                                  transformer_lm_decode_step,
                                                  transformer_lm_prefill)
        self.name = name
        self._model = model
        _, pos_mod, blocks, _, _, mha = lm_layout(model)  # validates layout
        if params is None:
            model._ensure_init()
            params, state = model._params, model._state
        self.max_seq_len = int(min(max_seq_len, pos_mod.max_len))
        if self.max_seq_len < 2:
            raise ValueError(f"max_seq_len must be >= 2: {self.max_seq_len}")
        self.max_prompt_len = int(max_prompt_len
                                  if max_prompt_len is not None
                                  else self.max_seq_len - 1)
        if not 1 <= self.max_prompt_len < self.max_seq_len:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} must leave room "
                f"for >= 1 generated token under max_seq_len "
                f"{self.max_seq_len}")
        self.eos_id = eos_id
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.queue_capacity = int(queue_capacity)
        self.deadline_s = (float(deadline_ms) / 1e3
                           if deadline_ms and deadline_ms > 0 else None)
        self.buckets = parse_row_buckets(prefill_buckets or "pow2@8",
                                         self.max_prompt_len)

        # KV budget: price the cache BEFORE allocating; the declared
        # budget wins over the requested slot count
        slots = int(slots)
        if slots < 1:
            raise ValueError(f"slots must be >= 1: {slots}")
        shape, dtype = kv_cache_spec(model, 1, self.max_seq_len)
        per_slot = 2 * int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        if kv_budget_mb is not None:
            afford = int(kv_budget_mb * (1 << 20)) // per_slot
            if afford < 1:
                raise ValueError(
                    f"kv_budget_mb={kv_budget_mb} cannot hold one slot "
                    f"({per_slot / (1 << 20):.2f} MB/slot at "
                    f"max_seq_len={self.max_seq_len})")
            slots = min(slots, afford)
        self.slots = slots
        self.kv_bytes = per_slot * slots

        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from bigdl_tpu.parallel.tensor_parallel import build_param_specs
            specs = build_param_specs(model, params)
            params = jax.tree_util.tree_map(
                lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
                params, specs)
        self._params = params
        self._mesh = mesh

        self.metrics = ServingMetrics(registry)
        reg = self.metrics.registry
        self._c_steps = reg.counter("decode/steps")
        self._c_tokens = reg.counter("decode/tokens_generated")
        self._c_admissions = reg.counter("decode/admissions")
        self._c_reclaims = reg.counter("decode/slots_reclaimed")
        self._c_active_steps = reg.counter("decode/active_slot_steps")
        self._h_queue_wait = reg.histogram("decode/queue_wait_ms")
        self._h_step = reg.histogram("decode/step_ms")
        self._c_kv_used = reg.counter("decode/kv_positions_used")
        self._c_kv_reserved = reg.counter("decode/kv_positions_reserved")
        self._c_prefill_tokens = reg.counter("decode/prefill_tokens")
        self._c_prefill_padded = reg.counter("decode/prefill_tokens_padded")
        self._c_first_fetch_bytes = reg.counter("decode/first_fetch_bytes")
        self._c_expired_queued = reg.counter("decode/expired_before_admit")
        self._c_expired_active = reg.counter("decode/expired_mid_decode")

        # the tracer: resolved ONCE here; ``None`` is the off state and
        # every span of the loop goes through ``_span``, which tests it
        if tracer is None:
            from bigdl_tpu.utils.config import get_config
            cfg = get_config()
            if cfg.telemetry_enabled:
                from bigdl_tpu.telemetry.tracer import Tracer
                tracer = Tracer(capacity=cfg.telemetry_trace_capacity)
        self.tracer = tracer if tracer is not None and tracer.enabled \
            else None
        # the spans' running state, one histogram a span name, fed as
        # each span closes (scheduler's thread) and read by ``stats()``
        self._span_hist = self._h_host_step = None
        if self.tracer is not None:
            self._span_hist = {n: reg.histogram(f"decode/span_ms/{n}")
                               for n in SPAN_CATS}
            self._h_host_step = reg.histogram("decode/host_step_ms")
        # the loop's start and the end of its last top-level span;
        # written by the scheduler only, read racily by ``stats()``
        self._loop_t0_ns = self._loop_t1_ns = 0
        # admin plane, as the predict engine registers: the registry,
        # and the tracer when one is held, under a name of its own
        from bigdl_tpu.telemetry import admin as _admin
        self._admin_name: Optional[str] = None
        _srv = _admin.maybe_start()
        if _srv is not None:
            self._admin_name = _srv.unique_source_name(self.name)
            _srv.add_registry(self._admin_name, reg)
            if self.tracer is not None:
                _srv.add_tracer(self._admin_name, self.tracer)

        self._priority_fn = priority_fn
        self._priority_aging_s = 0.5  # same starvation bound as batcher

        # ---- cross-thread state --------------------------------------
        self._cond = threading.Condition()
        self._queue: deque[_Pending] = deque()  # guarded-by: _cond
        self._n_active = 0       # guarded-by: _cond
        self._stopping = False   # guarded-by: _cond
        self._drain = True       # guarded-by: _cond
        self._n_submitted = 0    # guarded-by: _cond
        # written by the scheduler only; it reads its own count lock-free
        self._steps_done = 0     # write-guarded-by: _cond
        # step-seconds EWMA; written by the scheduler only, read racily
        # for overload retry hints (a stale hint is still a hint)
        self._step_ewma: Optional[float] = None
        self._thread: Optional[threading.Thread] = None  # guarded-by: _cond

        # ---- scheduler-thread-owned state (single owner: the decode
        # loop; constructed here before the thread exists) -------------
        self._seqs: List[Optional[_Sequence]] = [None] * slots
        self._lengths = np.zeros((slots,), np.int32)  # cached positions
        self._last_tok = np.zeros((slots,), np.int32)
        self._h2d_bytes = self._last_tok.nbytes + self._lengths.nbytes
        full, fdtype = kv_cache_spec(model, slots, self.max_seq_len)
        self._k = jnp.zeros(full, fdtype)
        self._v = jnp.zeros(full, fdtype)

        # ---- AOT executables -----------------------------------------
        # the PR-5 trace-count discipline: tracing happens ONLY during
        # this warmup; a steady-state retrace is a bug tests can gate on
        self._trace_count = 0

        def _prefill_fn(p, tokens):
            return transformer_lm_prefill(model, p, tokens)

        def _splice_fn(k, v, kp, vp, slot):
            # write a (L, 1, H, Tb, Dh) prefill cache into the slot
            k2 = jax.lax.dynamic_update_slice(k, kp, (0, slot, 0, 0, 0))
            v2 = jax.lax.dynamic_update_slice(v, vp, (0, slot, 0, 0, 0))
            return k2, v2

        def _step_fn(p, tokens, lengths, k, v):
            return transformer_lm_decode_step(model, p, tokens, lengths,
                                              k, v)

        def _aot(jitted, *avals):
            # compile counting lives HERE, in host code, not as a side
            # effect inside the traced functions: every executable is
            # `.lower().compile()`d exactly once per call of this
            # helper, and a Compiled object can never retrace — so
            # compile_count is frozen after the ctor by construction
            self._trace_count += 1
            return jitted.lower(*avals).compile()

        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        L, _, H, _, Dh = full
        if mesh is not None:
            # every KV seam carries ONE declared NamedSharding — the
            # slot cache, the per-bucket prefill outputs, and each
            # executable's in/out avals (heads over the model axis when
            # it divides them; logits and token vectors replicated).
            # Left to GSPMD, prefill picks a model-sharded output
            # layout while splice compiles for a single device, and the
            # AOT call is rejected at dispatch with a sharding
            # mismatch.
            m_sz = mesh.shape.get("model", 1)
            kv_axis = "model" if (m_sz > 1 and H % m_sz == 0) else None
            rep_sh = NamedSharding(mesh, P())
            kv_sh = NamedSharding(mesh,
                                  P(None, None, kv_axis, None, None))
            self._k = jax.device_put(self._k, kv_sh)
            self._v = jax.device_put(self._v, kv_sh)
            lkv_out = {"out_shardings": (rep_sh, kv_sh, kv_sh)}
            kv_out = {"out_shardings": (kv_sh, kv_sh)}
        else:
            rep_sh = kv_sh = None
            lkv_out = kv_out = {}
        kspec = sds(full, fdtype, sharding=kv_sh)
        # leaves handed to the step's executable a launch
        self._step_leaves = len(jax.tree_util.tree_leaves(self._params)) + 4
        self._step_exec = _aot(
            jax.jit(_step_fn, **lkv_out), self._params,
            sds((slots,), i32, sharding=rep_sh),
            sds((slots,), i32, sharding=rep_sh), kspec, kspec)
        jit_prefill = jax.jit(_prefill_fn, **lkv_out)
        jit_splice = jax.jit(_splice_fn, **kv_out)
        self._prefill_exec = {}
        self._splice_exec = {}
        for tb in self.buckets:
            pseq = sds((L, 1, H, tb, Dh), fdtype, sharding=kv_sh)
            self._prefill_exec[tb] = _aot(
                jit_prefill, self._params,
                sds((1, tb), i32, sharding=rep_sh))
            self._splice_exec[tb] = _aot(
                jit_splice, kspec, kspec, pseq, pseq,
                sds((), i32, sharding=rep_sh))

        if start:
            self.start()

    # ------------------------------------------------------------ control
    def start(self) -> "DecodeService":
        with self._cond:
            if self._thread is None:
                t = threading.Thread(target=self._run,
                                     name=f"decode-sched/{self.name}",
                                     daemon=True)
                self._thread = t
                t.start()
        return self

    @property
    def alive(self) -> bool:
        with self._cond:
            t = self._thread
        return t is not None and t.is_alive()

    @property
    def max_batch_size(self) -> int:
        """Slot capacity — the backend-contract name the frontend's
        request validators expect."""
        return self.slots

    @property
    def row_spec(self):
        """Backend-contract compatibility (``HotCutover`` / registry
        introspection): decode requests are token prompts, not fixed
        row shapes — there is no per-row spec to advertise."""
        return None

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    @property
    def steps_done(self) -> int:
        with self._cond:
            return self._steps_done

    def stop(self, drain: bool = True, timeout: Optional[float] = 30.0):
        """Refuse new work; with ``drain`` finish every queued + active
        sequence first, else cancel them (``ServiceClosed``)."""
        with self._cond:
            self._stopping = True
            self._drain = bool(drain)
            t = self._thread
            self._cond.notify_all()
        if t is not None:
            t.join(timeout)
        if self._admin_name is not None:
            from bigdl_tpu.telemetry import admin as _admin
            _srv = _admin.current()
            if _srv is not None:
                _srv.remove_source(self._admin_name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    # ------------------------------------------------------------- submit
    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               deadline: Optional[float] = None, ctx=None,
               on_token: Optional[Callable[[int, int], None]] = None):
        """Enqueue one prompt (1-D int array/list).  Returns a Future
        resolving to a :class:`DecodeResult`.  ``on_token(index,
        token_id)`` fires from the scheduler thread as each token is
        generated — it must not block (the streaming route hands tokens
        to its own writer).  ``deadline`` is absolute monotonic seconds
        (the frontend's ``X-Deadline-Ms`` path); default from
        ``deadline_ms``."""
        x = np.asarray(prompt)
        if x.ndim != 1 or x.size < 1 or not np.issubdtype(x.dtype,
                                                          np.integer):
            raise RequestSpecError(
                f"prompt must be a non-empty 1-D int array, got "
                f"shape {x.shape} dtype {x.dtype}")
        if x.size > self.max_prompt_len:
            raise RequestSpecError(
                f"prompt length {x.size} > max_prompt_len "
                f"{self.max_prompt_len}")
        max_new = (int(max_new_tokens) if max_new_tokens is not None
                   else self.default_max_new_tokens)
        if max_new < 1:
            raise RequestSpecError(f"max_new_tokens must be >= 1: "
                                   f"{max_new}")
        max_new = min(max_new, self.max_seq_len - int(x.size))
        if deadline is None and self.deadline_s is not None:
            deadline = time.monotonic() + self.deadline_s
        req = _Request(x.astype(np.int32), 1, deadline=deadline, ctx=ctx)
        pend = _Pending(req, max_new, on_token)
        if self.tracer is not None:
            pend.t_submit_ns = time.perf_counter_ns()
        with self._cond:
            if self._stopping:
                raise ServiceClosed(f"decode service {self.name!r} is "
                                    f"stopping")
            if len(self._queue) >= self.queue_capacity:
                self.metrics.record_reject(1)
                raise ServiceOverloaded(
                    len(self._queue), self.queue_capacity, self.name,
                    retry_after_ms=self._retry_hint_locked())
            self._n_submitted += 1
            pend.rid = (getattr(ctx, "trace_id", None)
                        or self._n_submitted)
            self._queue.append(pend)
            self._cond.notify_all()
        self.metrics.record_submit(1)
        return req.future

    def generate(self, prompt, **kw) -> DecodeResult:
        """Blocking sugar over :meth:`submit`."""
        return self.submit(prompt, **kw).result()

    def _retry_hint_locked(self) -> Optional[float]:  # guarded-by: _cond
        """Queue-drain estimate: steps to free a slot times step time.
        Coarse by design — a shed caller needs a magnitude, not a
        promise."""
        ew = self._step_ewma
        if ew is None:
            return None
        waves = (len(self._queue) + self.slots) / max(1, self.slots)
        return ew * 1e3 * waves * max(1, self.default_max_new_tokens // 4)

    # ---------------------------------------------------------- scheduler
    def _rank_locked(self, pend: _Pending, now: float) -> int:
        """The batcher's effective-rank rule verbatim: declared rank
        minus one class per aging period waited; a broken priority_fn
        ranks most-urgent instead of killing the scheduler."""
        try:
            rank = int(self._priority_fn(pend.req))
        except Exception:
            return 0
        return rank - int((now - pend.req.t_enqueue)
                          / self._priority_aging_s)

    # guarded-by: _cond
    def _pick_admissions_locked(self, free: int) -> List[_Pending]:
        """Pop up to ``free`` queued sequences.  FIFO under light load;
        with a ``priority_fn`` and more queued than admissible, best
        (effective rank, arrival) wins — the batcher's pressure rule at
        slot granularity."""
        if free <= 0 or not self._queue:
            return []
        picked: List[_Pending] = []
        pressure = (self._priority_fn is not None
                    and len(self._queue) > free)
        now = time.monotonic()
        for _ in range(min(free, len(self._queue))):
            if pressure:
                best = min(range(len(self._queue)),
                           key=lambda i: (self._rank_locked(
                               self._queue[i], now),
                               self._queue[i].req.t_enqueue))
                picked.append(self._queue[best])
                del self._queue[best]
            else:
                picked.append(self._queue.popleft())
        return picked

    def _span(self, name: str, **args):
        """A span of the scheduler's thread (``SPAN_CATS``); the shared
        no-op without a tracer (the driver's ``_tel_span`` discipline).
        Call sites pass only values they hold already."""
        tr = self.tracer
        if tr is None:
            return NULL_SPAN
        cat = SPAN_CATS[name]
        return _TimedSpan(tr.span(name, cat=cat, **args),
                          self._span_hist[name],
                          self if DECODE_PHASE_CATS[cat] else None)

    def _emit(self, seq: _Sequence, index: int, token: int) -> None:
        cb = seq.pend.on_token
        if cb is None:
            return
        try:
            cb(index, token)
        except Exception:
            logger.exception("decode on_token callback failed "
                             "(model=%s)", self.name)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _admit(self, pend: _Pending, slot: int) -> None:
        """Prefill one sequence into ``slot`` (scheduler thread)."""
        import jax.numpy as jnp
        req = pend.req
        prompt = req.x
        n = int(prompt.shape[0])
        tb = self._bucket_for(n)
        now = time.monotonic()
        wait_ms = (now - req.t_enqueue) * 1e3
        tr = self.tracer
        # one stamp ends the request's wait and starts its sequence
        t_admit_ns = time.perf_counter_ns() if tr is not None else 0
        with self._span("admit", req=pend.rid, slot=slot, prompt_len=n,
                        bucket=tb):
            if tr is not None:
                tr.record("queue_wait", pend.t_submit_ns, t_admit_ns,
                          cat="decode_queue", track="queue", req=pend.rid)
            if req.deadline is not None and now >= req.deadline:
                self._c_expired_queued.inc()
                if settle_future(req.future, exc=DeadlineExceeded(
                        f"deadline expired before admission "
                        f"(model={self.name})")):
                    self.metrics.record_failure(1)
                return
            self._h_queue_wait.observe(wait_ms)
            padded = np.zeros((1, tb), np.int32)
            padded[0, :n] = prompt
            with self._span("prefill_launch", bucket=tb):
                lp, kp, vp = self._prefill_exec[tb](self._params,
                                                    jnp.asarray(padded))
            with self._span("splice_launch", bucket=tb):
                self._k, self._v = self._splice_exec[tb](
                    self._k, self._v, kp, vp, np.int32(slot))
            self.metrics.record_dispatch(1, 1)  # prefill dispatch
            nbytes = int(lp.nbytes)
            with self._span("first_fetch", bytes=nbytes):
                lp_host = np.asarray(lp)  # waits for the prefill
            self._c_first_fetch_bytes.inc(nbytes)
            self._c_prefill_tokens.inc(n)
            self._c_prefill_padded.inc(tb)
            with self._span("emit"):
                first = int(lp_host[0, n - 1].argmax())
                with self._cond:
                    admit_step = self._steps_done
                    self._n_active += 1
                seq = _Sequence(pend, n, tb, admit_step, slot, t_admit_ns)
                self._seqs[slot] = seq
                self._lengths[slot] = n
                self._last_tok[slot] = first
                self._c_admissions.inc()
                seq.generated.append(first)
                self._c_tokens.inc()
                self._emit(seq, 0, first)
                # a 1-token request (or instant EOS) finishes without
                # ever joining the step batch
                self._maybe_finish(seq, first)

    def _close_sequence(self, seq: _Sequence, reason: str,
                        finish_step: int) -> None:
        """The request's ``sequence`` span, admission to finish, on its
        slot's track."""
        tr = self.tracer
        if tr is None:
            return
        tr.record(
            "sequence", seq.t_admit_ns, time.perf_counter_ns(),
            cat="decode_sequence", track=f"slot-{seq.slot}",
            req=seq.pend.rid, tokens=len(seq.generated), reason=reason,
            admit_step=seq.admit_step, finish_step=finish_step)

    def _finish(self, seq: _Sequence, reason: str) -> None:
        with self._cond:
            finish_step = self._steps_done
            self._n_active -= 1
            self._cond.notify_all()
        self._seqs[seq.slot] = None
        self._lengths[seq.slot] = 0
        self._last_tok[seq.slot] = 0
        self._c_reclaims.inc()
        self._close_sequence(seq, reason, finish_step)
        res = DecodeResult(np.asarray(seq.generated, np.int32), reason,
                           seq.admit_step, finish_step, seq.slot,
                           seq.prompt_len, seq.bucket)
        if settle_future(seq.pend.req.future, result=res):
            self.metrics.record_done(
                1, time.monotonic() - seq.pend.req.t_enqueue,
                bucket=seq.bucket)

    def _fail(self, seq: _Sequence, exc: BaseException) -> None:
        with self._cond:
            finish_step = self._steps_done
            self._n_active -= 1
            self._cond.notify_all()
        self._seqs[seq.slot] = None
        self._lengths[seq.slot] = 0
        self._last_tok[seq.slot] = 0
        self._c_reclaims.inc()
        self._close_sequence(seq, type(exc).__name__, finish_step)
        if settle_future(seq.pend.req.future, exc=exc):
            self.metrics.record_failure(1)

    def _maybe_finish(self, seq: _Sequence, token: int) -> bool:
        if self.eos_id is not None and token == self.eos_id:
            self._finish(seq, "eos")
            return True
        if len(seq.generated) >= seq.pend.max_new:
            self._finish(seq, "length")
            return True
        if seq.prompt_len + len(seq.generated) >= self.max_seq_len:
            self._finish(seq, "length")
            return True
        return False

    def _step(self) -> None:
        """One decode iteration over the slot batch (scheduler thread):
        every active sequence's last token is written to its cache and
        its next token decoded — ONE executable run regardless of how
        many sequences are active (the inactive lanes compute discarded
        garbage; occupancy is the metric that prices this)."""
        import jax.numpy as jnp
        tr = self.tracer
        t0 = time.monotonic()
        active = [s for s in self._seqs if s is not None]
        with self._span("step", step=self._steps_done,
                        active=len(active)) as st:
            # positions the step attends over (a free slot's length is
            # 0), against what the strips reserve
            self._c_kv_used.inc(int(self._lengths.sum()))
            self._c_kv_reserved.inc(self.slots * self.max_seq_len)
            with self._span("step_h2d", bytes=self._h2d_bytes):
                tokens = jnp.asarray(self._last_tok)
                lengths = jnp.asarray(self._lengths)
            with self._span("dispatch", args=self._step_leaves):
                lp, self._k, self._v = self._step_exec(
                    self._params, tokens, lengths, self._k, self._v)
            if tr is None:
                lp_host = np.asarray(lp)  # device sync point
            else:
                # the same fetch, split into the wait and the copy
                with self._span("device_wait") as wait:
                    lp.block_until_ready()
                with self._span("step_fetch", bytes=int(lp.nbytes)):
                    lp_host = np.asarray(lp)
            dt = time.monotonic() - t0
            self._step_ewma = (dt if self._step_ewma is None
                               else 0.8 * self._step_ewma + 0.2 * dt)
            self._h_step.observe(dt * 1e3)
            with self._cond:
                self._steps_done += 1
            self._c_steps.inc()
            self._c_active_steps.inc(len(active))
            self.metrics.record_dispatch(len(active), self.slots)
            with self._span("emit") as sp:
                now = time.monotonic()
                emitted = 0
                for seq in active:
                    # cache grew by one position (the step wrote
                    # last_tok's K/V)
                    self._lengths[seq.slot] += 1
                    if (seq.pend.req.deadline is not None
                            and now >= seq.pend.req.deadline):
                        self._c_expired_active.inc()
                        self._fail(seq, DeadlineExceeded(
                            f"deadline expired mid-decode after "
                            f"{len(seq.generated)} tokens "
                            f"(model={self.name})"))
                        continue
                    tok = int(lp_host[seq.slot].argmax())
                    self._last_tok[seq.slot] = tok
                    seq.generated.append(tok)
                    self._c_tokens.inc()
                    emitted += 1
                    self._emit(seq, len(seq.generated) - 1, tok)
                    self._maybe_finish(seq, tok)
                sp.set(tokens=emitted)
        if tr is not None:
            # what the host adds to this step: the step less its wait
            self._h_host_step.observe((st.dur_ns - wait.dur_ns) / 1e6)

    def _idle_locked(self) -> bool:  # guarded-by: _cond
        """Nothing queued, nothing active, no stop asked for."""
        return (not self._stopping and not self._queue
                and self._n_active == 0)

    def _cancel_backlog_locked(self) -> List[_Pending]:  # guarded-by: _cond
        out = list(self._queue)
        self._queue.clear()
        return out

    def _run(self) -> None:
        """The decode loop.  Each pass: admit queued sequences into free
        slots (prefill off the lock), then run one step if anything is
        active.  Blocks on the condition when idle.  Four top-level
        spans tile a pass: ``idle``, ``schedule``, ``admit``, ``step``
        (``DECODE_PHASE_CATS``).  An unexpected
        exception anywhere in the loop fails every in-flight future
        with it instead of dying silently — a crashed scheduler with
        live futures would park every ``generate()`` caller forever."""
        cancelled: List[_Pending] = []
        crash: Optional[BaseException] = None
        if self.tracer is not None:
            self._loop_t0_ns = self._loop_t1_ns = time.perf_counter_ns()
        try:
            while True:
                with self._cond:
                    if self._idle_locked():
                        with self._span("idle"):
                            while self._idle_locked():
                                self._cond.wait()
                    free = self.slots - self._n_active
                    with self._span("schedule", queued=len(self._queue),
                                    free=free) as sp:
                        if self._stopping and (
                                not self._drain
                                or (not self._queue
                                    and self._n_active == 0)):
                            cancelled = self._cancel_backlog_locked()
                            break
                        to_admit = self._pick_admissions_locked(free)
                        sp.set(picked=len(to_admit))
                for slot in range(self.slots):
                    if not to_admit:
                        break
                    if self._seqs[slot] is None:
                        self._admit(to_admit.pop(0), slot)
                if any(s is not None for s in self._seqs):
                    self._step()
        except Exception as e:
            logger.exception("decode scheduler crashed (model=%s)",
                             self.name)
            crash = e
            with self._cond:
                self._stopping = True  # submit() refuses from here on
                cancelled = self._cancel_backlog_locked()
                self._cond.notify_all()
        # non-drain stop (or crash): settle queued work and active
        # sequences — the crash exception propagates to every caller
        exc = crash if crash is not None else ServiceClosed(
            f"decode service {self.name!r} stopped")
        for pend in cancelled:
            if settle_future(pend.req.future, exc=exc):
                if crash is None:
                    self.metrics.record_cancel(1)
                else:
                    self.metrics.record_failure(1)
        for seq in list(self._seqs):
            if seq is not None:
                self._fail(seq, exc)

    # -------------------------------------------------------------- stats
    def _span_stats(self) -> dict:
        """The spans' running state: per span name its category, exact
        seconds and count since the start and the median of the newest
        4,096; a step less its ``device_wait``, paired as the step
        closed; and the share of the scheduler's time, from its loop's
        start to the end of its last top-level span, that no top-level
        span covered."""
        # the window's end before the sums: a span that closes between
        # the two reads can only add to what is covered
        loop_ms = (self._loop_t1_ns - self._loop_t0_ns) / 1e6
        spans = {}
        covered_ms = 0.0
        for name, h in self._span_hist.items():
            if not h.count:
                continue
            cat = SPAN_CATS[name]
            spans[name] = {"cat": cat, "seconds": h.sum / 1e3,
                           "spans": h.count,
                           "median_ms": h.percentiles((50,))["p50"]}
            if DECODE_PHASE_CATS[cat]:
                covered_ms += h.sum
        host = self._h_host_step.percentiles((50,))
        return {
            "spans": spans,
            "host_step_ms": host["p50"] if host else None,
            "loop_unspanned_share": (max(0.0, 1.0 - covered_ms / loop_ms)
                                     if loop_ms > 0 else None),
            "trace_dropped_events": self.tracer.dropped_events,
        }

    def stats(self) -> dict:
        """The ``service.stats()`` schema plus a ``decode`` section:
        step/token/admission accounting, step-level occupancy
        (active-slot-steps over total slot-steps — the continuous-
        batching utilization figure), the counters and histograms of
        the module docstring, and with a tracer the spans' running
        sums (:meth:`_span_stats`).  Counts run since the service
        started."""
        with self._cond:
            qd = len(self._queue)
            steps = self._steps_done
            active = self._n_active
        snap = self.metrics.snapshot(queue_depth=qd,
                                     compile_count=self._trace_count)
        ew = self._step_ewma
        snap["decode"] = {
            "slots": self.slots,
            "active": active,
            "steps": steps,
            "tokens_generated": self._c_tokens.value,
            "admissions": self._c_admissions.value,
            "slots_reclaimed": self._c_reclaims.value,
            "step_occupancy": (
                round(self._c_active_steps.value / (steps * self.slots), 4)
                if steps else None),
            # for overload retry hints only; read ``step_ms``
            "step_ms_ewma": round(ew * 1e3, 3) if ew is not None else None,
            "step_ms": self._h_step.snapshot(),
            "queue_wait_ms": self._h_queue_wait.snapshot(),
            "kv_positions_used": self._c_kv_used.value,
            "kv_positions_reserved": self._c_kv_reserved.value,
            "prefill_tokens": self._c_prefill_tokens.value,
            "prefill_tokens_padded": self._c_prefill_padded.value,
            "first_fetch_bytes": self._c_first_fetch_bytes.value,
            "expired_before_admit": self._c_expired_queued.value,
            "expired_mid_decode": self._c_expired_active.value,
            "prefill_buckets": list(self.buckets),
            "max_seq_len": self.max_seq_len,
            "kv_bytes": self.kv_bytes,
        }
        if self.tracer is not None:
            snap["decode"].update(self._span_stats())
        return snap
