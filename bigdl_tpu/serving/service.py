"""InferenceService — dynamic batching over AOT-compiled bucket executables.

The TPU-native serving contract (README "serving"):

- **One compiled forward per row-bucket, compiled at deploy time.**
  Steady-state traffic must never trace or compile: coalesced batches
  are padded up to the nearest power-of-two row bucket and every bucket
  executable is built up-front with ``jax.jit(...).lower(...).compile()``
  — the same recompile-hazard discipline graftlint GL106 enforces for
  training loops, applied to the serving path (catalog note in
  ``tools/graftlint/README.md``).
- **Zero padding, sliced off.**  Padded rows are zeros, never copies of
  real rows: the invariant inference relies on is that the forward is
  row-independent in eval mode (BatchNorm uses running stats, dropout is
  off), so pad values cannot leak into real rows and are simply sliced
  away.  Zeros keep the H2D transfer compressible and make the invariant
  auditable — a pad row that *did* influence output would change results
  between bucket sizes, which the serving tests gate bitwise.
- **Futures in, backpressure out.**  ``submit`` enqueues and returns a
  ``concurrent.futures.Future``; a full bounded queue raises
  ``ServiceOverloaded`` (queue depth in the message) instead of
  buffering into timeout territory.  ``predict`` is the blocking sugar
  (and chunks oversized inputs across several requests).
"""

from __future__ import annotations

import threading
import time
import weakref
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.serving.batcher import (
    DeadlineExceeded, RequestBatcher, RequestSpecError, ServiceClosed,
    ServiceOverloaded, _Request, settle_future,
)
from bigdl_tpu.serving.metrics import ServingMetrics

_tree = jax.tree_util


def row_buckets(max_batch_size: int, floor: int = 1) -> Tuple[int, ...]:
    """Power-of-two row buckets up to ``max_batch_size`` (inclusive —
    a non-power-of-two max becomes the top bucket so a full coalesced
    batch never spills into two dispatches).  ``floor`` starts the
    ladder higher than 1 — sequence-length ladders (decode prefill)
    have no use for 1/2/4-token executables."""
    bs = []
    b = max(1, int(floor))
    while b < max_batch_size:
        bs.append(b)
        b *= 2
    bs.append(max_batch_size)
    return tuple(bs)


def parse_row_buckets(spec: str, max_batch_size: int) -> Tuple[int, ...]:
    """Parse a ``Config.serving_row_buckets`` bucket-set spec:

    - ``""`` / ``"pow2"`` — :func:`row_buckets` power-of-two auto (the
      default);
    - ``"top"`` — one bucket at ``max_batch_size`` (maximum executable
      sharing, maximum padding);
    - ``"pow2@16"`` — power-of-two ladder FLOORED at 16: the
      sequence-length form of the grammar (decode prefill buckets in
      ``serving/decode.py``, where ``max_batch_size`` is the max
      prompt length and sub-floor executables are wasted compiles);
    - ``"8,16,32"`` — explicit ascending positive ints whose top must
      cover ``max_batch_size`` (a full coalesced batch always has a
      bucket to pad into).
    """
    s = (spec or "").strip()
    if s in ("", "pow2"):
        return row_buckets(max_batch_size)
    if s == "top":
        return (max_batch_size,)
    if s.startswith("pow2@"):
        try:
            floor = int(s[5:])
        except ValueError:
            raise ValueError(
                f"bucket spec {spec!r}: pow2@<floor> needs an int "
                f"floor") from None
        if floor < 1:
            raise ValueError(f"bucket floor must be >= 1: {floor}")
        return row_buckets(max_batch_size, floor)
    try:
        buckets = tuple(int(tok) for tok in s.split(","))
    except ValueError:
        raise ValueError(
            f"row-bucket spec {spec!r} must be '', 'pow2', 'top' or a "
            f"comma-separated int list") from None
    if (not buckets or any(b < 1 for b in buckets)
            or list(buckets) != sorted(set(buckets))):
        raise ValueError(
            f"row buckets {buckets} must be ascending unique positive "
            f"ints")
    if buckets[-1] < max_batch_size:
        raise ValueError(
            f"top row bucket {buckets[-1]} < max_batch_size "
            f"{max_batch_size} — a full coalesced batch would have no "
            f"bucket to pad into")
    return buckets


def leading_rows(x) -> int:
    # RequestSpecError (a ValueError): the REQUEST is malformed — the
    # wire frontend maps it to 400 instead of a server-fault 500
    leaves = _tree.tree_leaves(x)
    if not leaves:
        raise RequestSpecError("empty input pytree")
    n = leaves[0].shape[0] if leaves[0].ndim else None
    for leaf in leaves:
        if leaf.ndim == 0 or leaf.shape[0] != n:
            raise RequestSpecError(
                "all input leaves must share one leading batch dim; got "
                f"shapes {[leaf.shape for leaf in leaves]}")
    return n


def pad_rows(x, target: int):
    """Zero-pad every leaf's leading dim up to ``target`` rows (see the
    module docstring for why zeros and not row copies)."""

    def pad(leaf):
        n = leaf.shape[0]
        if n == target:
            return leaf
        widths = [(0, target - n)] + [(0, 0)] * (leaf.ndim - 1)
        return np.pad(leaf, widths)

    return _tree.tree_map(pad, x)


def _detect_weights_dtype(model, params) -> str:
    """Classify the served model's weight storage: ``"int8"`` when any
    quantized twin (``nn.quantized``) is in the module tree, else
    ``"bf16"``/``"f32"`` from the param leaves.  Host-side, walked once
    at service construction — the ``weights_dtype`` tag the int8
    serving rollout gates on (stats()/``/metrics``)."""
    from bigdl_tpu.nn.module import Container
    from bigdl_tpu.nn.quantized import (QuantizedLinear,
                                        QuantizedSpatialConvolution,
                                        _QuantizedCellBase)
    from bigdl_tpu.nn.recurrent import BiRecurrent, Recurrent
    stack = [model]
    while stack:
        m = stack.pop()
        if isinstance(m, (QuantizedLinear, QuantizedSpatialConvolution,
                          _QuantizedCellBase)):
            return "int8"
        if isinstance(m, Container):
            stack.extend(m.modules)
        elif isinstance(m, Recurrent):
            stack.append(m.cell)
        elif isinstance(m, BiRecurrent):
            stack.extend((m.fwd, m.bwd))
    for leaf in jax.tree_util.tree_leaves(params):
        if getattr(leaf, "dtype", None) == jnp.bfloat16:
            return "bf16"
    return "f32"


class InferenceService:
    """Always-on inference endpoint for one model.

    Parameters
    ----------
    model, params, state:
        Any :class:`~bigdl_tpu.nn.module.Module` (including the
        ``nn.quantized`` int8 twins and interop-loaded models); params
        default to the model's own initialized weights.
    input_spec:
        Pytree of per-ROW ``jax.ShapeDtypeStruct`` (no batch dim) — or
        ``(shape, dtype)`` tuples / np arrays — describing one request
        row.  When given, all bucket executables are AOT-compiled at
        construction (deploy-time warmup); when ``None``, the spec is
        captured from the first request and warmup happens then (the
        back-compat ``PredictionService`` path).
    max_batch_size / batch_timeout_ms / queue_capacity / buckets:
        Coalescing and backpressure knobs; ``None`` resolves from
        ``Engine.serving_defaults()`` (config ``serving_*`` fields /
        ``BIGDL_TPU_SERVING_*`` env).
        ``buckets`` is either an explicit ascending int tuple or a
        :func:`parse_row_buckets` spec string ("pow2" / "top" /
        "8,16,32").
    start:
        ``start=False`` builds the service with the batcher parked —
        requests queue (bounded) until :meth:`start`.  Used by tests to
        stage deterministic coalescing, and by deploys that want warmup
        strictly before traffic.
    fault_injector:
        Optional :class:`~bigdl_tpu.resilience.faults.FaultInjector`
        consulted once per coalesced dispatch (keyed by this service's
        own dispatch counter) — the chaos hook the resilience tests
        drive.  ``None`` (the default) is the provably-inert state: the
        dispatch path never touches it.
    priority_fn:
        Optional QoS preemption hook handed to the
        :class:`~bigdl_tpu.serving.batcher.RequestBatcher`: maps an
        enqueued request (it carries ``.ctx`` with the tenant tag) to
        an int rank, lower dispatching first — engaged only when the
        queue holds more rows than one dispatch can carry.  ``None``
        (the default) keeps the batcher byte-identical FIFO.  The
        frontend's :class:`~bigdl_tpu.frontend.QosAdmission` supplies
        its ``priority_fn`` here.
    tracer / request_tracing:
        Request-scoped observability (telemetry round 2).  ``tracer``
        is an optional :class:`~bigdl_tpu.telemetry.Tracer` — submit
        and dispatch land as spans, with Chrome flow events fanning
        the N coalesced request spans into their one dispatch span.
        ``request_tracing`` (None = ``Config.request_tracing``) mints a
        :class:`~bigdl_tpu.telemetry.RequestContext` per submit when no
        explicit context is passed; off (the default), no context is
        ever allocated and the request path is byte-identical.
    """

    def __init__(self, model, params=None, state=None, *,
                 input_spec=None, max_batch_size: Optional[int] = None,
                 batch_timeout_ms: Optional[float] = None,
                 queue_capacity: Optional[int] = None,
                 buckets=None, name: str = "model", start: bool = True,
                 fault_injector=None, tracer=None,
                 request_tracing: Optional[bool] = None,
                 priority_fn=None):
        from bigdl_tpu.engine import Engine
        defaults = Engine.serving_defaults()
        self.model = model
        if params is None:
            model._ensure_init()
            params, state = model._params, model._state
        self.params = params
        self.state = state if state is not None else {}
        self.name = name
        # `is not None` throughout: an explicit 0 must reach the
        # batcher's >= 1 validation, not silently become the default
        self.max_batch_size = int(
            max_batch_size if max_batch_size is not None
            else defaults["max_batch_size"])
        self.batch_timeout_ms = float(
            batch_timeout_ms if batch_timeout_ms is not None
            else defaults["batch_timeout_ms"])
        self.queue_capacity = int(
            queue_capacity if queue_capacity is not None
            else defaults["queue_capacity"])
        if buckets is None:
            buckets = defaults.get("row_buckets", "")
        if isinstance(buckets, str):
            self.buckets = parse_row_buckets(buckets, self.max_batch_size)
        else:
            # explicit tuple takes the same validation path: round-trip
            # through the spec grammar so ad-hoc bucket sets obey the
            # ascending/top-covers-max invariants too
            self.buckets = parse_row_buckets(
                ",".join(str(int(b)) for b in buckets),
                self.max_batch_size)

        # the ONE jit for this model; bucket executables are AOT builds
        # of it.  _trace_count counts Python traces — after warmup it
        # must never move (gated in tests/test_serving.py).
        self._trace_count = 0

        def fwd(params, state, x):
            # trace-time side effect BY DESIGN: runs once per Python
            # trace (= per compile), never in the compiled program —
            # it's the compile counter the zero-recompile gate reads
            self._trace_count += 1  # graftlint: disable=GL103
            out, _ = model.apply(params, state, x, training=False)
            return out

        self._jit = jax.jit(fwd)
        self._warm_lock = threading.Lock()
        # warmup state: written only under _warm_lock (warmup is the
        # one writer); hot-path reads are lock-free and gated on the
        # _warmed flag flipping LAST — readers never see a
        # partially-populated bucket dict
        self._compiled: Dict[int, Any] = {}  # write-guarded-by: _warm_lock
        self._warmed = False                 # write-guarded-by: _warm_lock
        self._row_spec = None                # write-guarded-by: _warm_lock
        self._out_spec = None                # write-guarded-by: _warm_lock
        # write-guarded-by: _warm_lock
        self._out_row_shape: Optional[Tuple[int, ...]] = None
        # serializes batcher replacement vs shutdown: revive() (on a
        # supervisor/failover thread) swaps in a new batcher and
        # start()s it; a concurrent stop() must never observe the new
        # thread object between creation and start() completing — a
        # join() there raises "cannot join thread before it is
        # started" (race surfaced by the obs-plane failover tests)
        self._lifecycle_lock = threading.Lock()
        self._stopped = False  # write-guarded-by: _lifecycle_lock
        self.metrics = ServingMetrics()
        # weights-dtype tag (int8 speed-path PR): detected once here,
        # surfaced in stats() and the pre-created /metrics gauge so the
        # registry's per-version rollout gates can see WHAT dtype each
        # deployed version serves (absent in old snapshots = "f32")
        self.weights_dtype = _detect_weights_dtype(model, self.params)
        self.metrics.set_weights_dtype(self.weights_dtype)
        # fault injection (resilience layer): the injector is consulted
        # per dispatch; _fault_replica is stamped by ReplicaSet so
        # target= clauses can aim at one replica of a set
        self._faults = fault_injector
        self._fault_replica: Optional[int] = None
        self._dispatch_index = 0
        self._priority_fn = priority_fn
        # request-scoped observability (telemetry round 2): resolved
        # ONCE here — the submit/dispatch hot paths only test the
        # resulting attributes, never read config
        self.tracer = tracer
        if request_tracing is None:
            from bigdl_tpu.utils.config import get_config
            request_tracing = get_config().request_tracing
        self._request_tracing = bool(request_tracing)
        # admin plane: config-driven start (admin_port=0 → None, no
        # thread) and source registration.  The scrape name is minted
        # unique (two same-named services must not evict each other,
        # and THIS service's stop() must only deregister a name it
        # owns); a retired name is released for the next deploy.
        from bigdl_tpu.telemetry import admin as _admin
        self._admin_name: Optional[str] = None
        _srv = _admin.maybe_start()
        if _srv is not None:
            self._admin_name = _srv.unique_source_name(self.name)
            _srv.add_registry(self._admin_name, self.metrics.registry)
            if self.tracer is not None:
                _srv.add_tracer(self._admin_name, self.tracer)
        # the batcher/finalizer pair is swapped atomically by revive()
        # and retired by stop(), both under the lifecycle lock; readers
        # (submit, queue_depth, alive) take the racy-by-design stale
        # reference — a put() into a just-retired batcher raises
        # ServiceClosed, which the caller already handles
        self._batcher = self._make_batcher()  # write-guarded-by: _lifecycle_lock
        # write-guarded-by: _lifecycle_lock
        self._finalizer = weakref.finalize(
            self, RequestBatcher.close, self._batcher, True, 5.0)
        if input_spec is not None:
            self.warmup(input_spec)
        if start:
            self._batcher.start()

    def _make_batcher(self) -> RequestBatcher:
        # a dropped service must not strand its batcher thread for the
        # life of the process (the historical PredictionService needed
        # no cleanup, so shim users never call stop()).  For the
        # finalizer to ever fire, the RUNNING thread must not pin the
        # service: the batcher gets a WeakMethod shim instead of the
        # bound `self._dispatch` (the ThreadPoolExecutor pattern) and
        # the finalize callback closes over the batcher only.  Corner
        # case (documented): a future whose service was garbage
        # collected before its dispatch resolves as cancelled — only
        # reachable by dropping every service reference while blocked
        # on result(), which predict() can't do (it holds `self`).
        weak_dispatch = weakref.WeakMethod(self._dispatch)

        def dispatch(requests):
            fn = weak_dispatch()
            if fn is None:  # service collected: nothing can resolve these
                for r in requests:
                    r.future.cancel()
                return
            fn(requests)

        return RequestBatcher(
            dispatch, max_batch_size=self.max_batch_size,
            batch_timeout_ms=self.batch_timeout_ms,
            queue_capacity=self.queue_capacity, name=self.name,
            priority_fn=self._priority_fn)

    # -- warmup ------------------------------------------------------------
    @staticmethod
    def _normalize_row_spec(input_spec):
        # a (shape, dtype) pair is a LEAF only when shape is a flat
        # tuple/list of ints — ``(((6,), f32), ((5,), f32))`` stays a
        # two-leaf pytree, not a shape of ((6,), f32)
        def is_pair(x):
            return (isinstance(x, tuple) and len(x) == 2
                    and isinstance(x[0], (tuple, list))
                    and all(isinstance(d, (int, np.integer))
                            for d in x[0]))

        def norm(leaf):
            if isinstance(leaf, jax.ShapeDtypeStruct):
                return leaf
            if is_pair(leaf):
                return jax.ShapeDtypeStruct(tuple(leaf[0]),
                                            jnp.dtype(leaf[1]))
            arr = np.asarray(leaf)
            return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

        is_leaf = (lambda x: isinstance(x, (jax.ShapeDtypeStruct,
                                            np.ndarray)) or is_pair(x))
        return _tree.tree_map(norm, input_spec, is_leaf=is_leaf)

    def warmup(self, input_spec) -> dict:
        """AOT-compile every row bucket (idempotent).  Returns
        ``{bucket: compile_seconds}`` so deploy logs can record the
        warmup bill."""
        with self._warm_lock:
            # gate on the all-buckets-ready flag, NOT on _compiled
            # being non-empty: a concurrent submitter seeing a
            # partially-populated dict would dispatch into a KeyError
            if self._warmed:
                return {}
            row = self._normalize_row_spec(input_spec)
            # output row shape via abstract eval — no device work, runs
            # BEFORE any compile.  The coalescing contract REQUIRES
            # output rows to follow input rows (dispatch slices
            # per-request outputs by input-row offsets), so a model
            # whose output rows come from static metadata (COO
            # dense_shape, pooling-over-batch) must be refused at
            # deploy — without paying the bucket compile bill — not
            # silently mis-sliced per request; two probe sizes so a
            # coincidental match can't slip by.
            for k in (1, 2):
                speck = _tree.tree_map(
                    lambda s, _k=k: jax.ShapeDtypeStruct(
                        (_k,) + s.shape, s.dtype), row)
                out = jax.eval_shape(self._jit, self.params, self.state,
                                     speck)
                bad = [tuple(o.shape) for o in _tree.tree_leaves(out)
                       if o.shape[:1] != (k,)]
                if bad:
                    raise ValueError(
                        f"model {self.name!r} is not servable by the "
                        f"coalescing engine: output leading dims {bad} "
                        f"do not track the input batch dim ({k} rows "
                        "in) — per-request output slicing would return "
                        "garbage.  Serve it behind a custom batcher or "
                        "use Predictor for whole-dataset inference")
            self._row_spec = row
            timings = {}
            for b in self.buckets:
                spec = _tree.tree_map(
                    lambda s: jax.ShapeDtypeStruct((b,) + s.shape, s.dtype),
                    row)
                t0 = time.monotonic()
                # deploy-time compile DELIBERATELY under the warm lock:
                # serializing concurrent first-submitters until every
                # bucket executable exists is the warmup contract (a
                # half-warmed dict KeyErrors) — the one reviewed
                # blocking-under-lock exception in the serving stack
                # graftlint: disable=GL206
                self._compiled[b] = self._jit.lower(
                    self.params, self.state, spec).compile()
                timings[b] = round(time.monotonic() - t0, 4)
            self._out_spec = _tree.tree_map(
                lambda o: jax.ShapeDtypeStruct(tuple(o.shape[1:]), o.dtype),
                out)
            leaves = _tree.tree_leaves(self._out_spec)
            self._out_row_shape = (tuple(leaves[0].shape)
                                   if len(leaves) == 1 else None)
            self._warmed = True
            return timings

    @property
    def warmed_up(self) -> bool:
        return self._warmed

    @property
    def compile_count(self) -> int:
        """Python traces of the forward so far.  Frozen after warmup in
        steady state — the serving analog of the GL106 gate."""
        return self._trace_count

    def output_row_shape(self) -> Optional[Tuple[int, ...]]:
        """Trailing dims of one output row (known after warmup)."""
        return self._out_row_shape

    @property
    def row_spec(self):
        """The warmed per-row input spec (pytree of
        ``jax.ShapeDtypeStruct``), or None before warmup — reusable as
        another service's ``input_spec`` (ReplicaSet grow and hot
        cutover both warm new executables off this)."""
        return self._row_spec

    @property
    def drain_ewma_s(self) -> Optional[float]:
        """The batcher's observed seconds-per-request EWMA (None before
        its first dispatch) — the drain-rate signal ``retry_after_ms``
        hints and the frontend autoscaler read.  Racy-by-design single
        read of a single-writer float."""
        return self._batcher._spr_ewma

    # -- request path ------------------------------------------------------
    def _normalize_input(self, x):
        xs = _tree.tree_map(np.asarray, x)
        n = leading_rows(xs)
        return xs, n

    def _conform_request(self, xs):
        """Validate a request against the warmed row spec BEFORE it can
        join a coalesced group: a malformed request must fail alone at
        submit, not poison every innocent caller batched with it
        (np.concatenate would either raise for the whole group or
        silently promote everyone's dtype).  Trailing-shape or
        tree-structure mismatch raises; dtype mismatch is coerced to
        the spec dtype (the historical ``jnp.asarray`` behavior — e.g.
        a float64 numpy default quietly serves as f32)."""
        spec_leaves, spec_def = _tree.tree_flatten(self._row_spec)
        req_leaves, req_def = _tree.tree_flatten(xs)
        if spec_def != req_def or any(
                leaf.shape[1:] != tuple(s.shape)
                for leaf, s in zip(req_leaves, spec_leaves)):
            raise RequestSpecError(
                f"request does not match the deployed input_spec of "
                f"{self.name!r}: expected per-row "
                f"{[(tuple(s.shape), str(s.dtype)) for s in spec_leaves]}"
                f", got {[leaf.shape[1:] for leaf in req_leaves]}")
        try:
            conformed = [leaf if leaf.dtype == s.dtype
                         else np.asarray(leaf, dtype=s.dtype)
                         for leaf, s in zip(req_leaves, spec_leaves)]
        except (ValueError, TypeError) as e:
            # data the spec dtype refuses (e.g. strings into f32) is
            # the request's fault, same as a shape mismatch
            raise RequestSpecError(
                f"request data does not coerce to the deployed "
                f"input_spec dtypes of {self.name!r}: {e}") from None
        return _tree.tree_unflatten(req_def, conformed)

    def submit(self, x, *, deadline: Optional[float] = None,
               ctx=None) -> Future:
        """Enqueue one request (pytree of arrays, shared leading batch
        dim ``n`` with ``1 <= n <= max_batch_size``) and return the
        Future of its stacked outputs.  Raises
        :class:`ServiceOverloaded` when the bounded queue is full and
        :class:`ServiceClosed` after :meth:`stop`.

        ``deadline`` (absolute ``time.monotonic()`` seconds, or None)
        travels WITH the request through the queue: the dispatch path
        refuses expired work with :class:`DeadlineExceeded` instead of
        burning device time on a caller that has given up — the
        per-request deadline propagation ``ReplicaSet`` routes on.

        ``ctx`` is an optional :class:`~bigdl_tpu.telemetry.
        RequestContext`; with ``request_tracing`` on and ``ctx=None``
        one is minted here.  It rides the queue with the request — the
        dispatch span flow-links back to this submit's span, and a
        router appends its hop history."""
        xs, n = self._normalize_input(x)
        if n == 0:
            f: Future = Future()
            f.set_result(self._empty_output())
            return f
        if n > self.max_batch_size:
            raise RequestSpecError(
                f"request of {n} rows exceeds max_batch_size="
                f"{self.max_batch_size}; use predict() which chunks")
        if deadline is not None and time.monotonic() >= deadline:
            # already expired: resolve without ever touching the queue
            f = Future()
            f.set_exception(DeadlineExceeded(
                f"request deadline passed before submit to "
                f"{self.name!r}"))
            return f
        if not self._warmed:
            # deferred-spec path: capture the row spec from live
            # traffic (warmup is lock-idempotent, so concurrent first
            # requests all block until EVERY bucket is compiled)
            self.warmup(_tree.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), xs))
        xs = self._conform_request(xs)
        if ctx is None and self._request_tracing:
            from bigdl_tpu.telemetry.context import RequestContext
            ctx = RequestContext(deadline=deadline)
        req = _Request(xs, n, deadline=deadline, ctx=ctx)
        tracer = self.tracer
        if ctx is not None and tracer is not None and tracer.enabled:
            # the request's submit span, with the outbound half of the
            # fan-in flow arrow the dispatch span will close
            with tracer.span("request_submit", cat="serving",
                             trace_id=ctx.trace_id, model=self.name,
                             rows=n, tenant=ctx.tenant):
                tracer.flow_start("req", ctx.flow_id, cat="serving")
                self._put_counted(req, n)
        else:
            self._put_counted(req, n)
        return req.future

    def _put_counted(self, req: _Request, n: int) -> None:
        try:
            self._batcher.put(req)
        except ServiceOverloaded:
            self.metrics.record_reject(n)
            raise
        self.metrics.record_submit(n)

    def predict(self, x, timeout: Optional[float] = None):
        """Blocking sugar over :meth:`submit`; chunks inputs larger than
        ``max_batch_size`` across several coalescible requests.

        ``timeout`` bounds the WHOLE call (a shared deadline across
        chunk futures, not per-future).  Chunks are submitted through a
        bounded in-flight window (≤ half the queue capacity), so an
        arbitrarily large input never self-overflows the bounded queue
        the way a submit-everything loop would; overloads caused by
        *other* callers are absorbed by draining one in-flight chunk
        and retrying."""
        xs, n = self._normalize_input(x)
        if n == 0:
            return self._empty_output()
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)

        def remaining() -> Optional[float]:
            if deadline is None:
                return None
            return max(0.0, deadline - time.monotonic())

        if n <= self.max_batch_size:
            return self.submit(xs).result(remaining())
        window = max(1, self.queue_capacity // 2)
        parts: List[Any] = []
        inflight: List[Future] = []
        for off in range(0, n, self.max_batch_size):
            lo, hi = off, off + self.max_batch_size
            chunk = _tree.tree_map(lambda a: a[lo:hi], xs)
            if len(inflight) >= window:
                parts.append(inflight.pop(0).result(remaining()))
            while True:
                try:
                    inflight.append(self.submit(chunk))
                    break
                except ServiceOverloaded:
                    if not inflight:  # foreign traffic owns the queue
                        raise
                    parts.append(inflight.pop(0).result(remaining()))
        parts.extend(f.result(remaining()) for f in inflight)
        return _tree.tree_map(
            lambda *ps: np.concatenate(ps, axis=0), *parts)

    def _empty_output(self):
        if self._out_spec is None:
            return np.empty((0,))
        return _tree.tree_map(
            lambda s: np.empty((0,) + tuple(s.shape), dtype=s.dtype),
            self._out_spec)

    # -- batcher callback --------------------------------------------------
    def _bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        return self.buckets[-1]

    def _dispatch(self, requests: List[_Request]) -> None:
        """Runs on the batcher thread: coalesce → pad to bucket → one
        compiled call → slice per-request outputs → resolve futures."""
        live = []
        for r in requests:
            try:
                if r.future.set_running_or_notify_cancel():
                    live.append(r)
            except Exception:
                # already resolved from OUTSIDE the batcher (the
                # ReplicaSet supervisor timing out / failing over a
                # stuck request) — nothing left to serve here
                pass
        if not live:
            return
        now = time.monotonic()
        expired = [r for r in live
                   if r.deadline is not None and now >= r.deadline]
        if expired:
            # deadline propagation: refuse expired work BEFORE the
            # device call — inference is idempotent, so the router may
            # already have retried it on another replica
            for r in expired:
                if settle_future(r.future, exc=DeadlineExceeded(
                        f"request expired in {self.name!r} queue after "
                        f"{(now - r.t_enqueue) * 1e3:.1f} ms")):
                    self.metrics.record_failure(r.n_rows)
            live = [r for r in live
                    if r.deadline is None or now < r.deadline]
            if not live:
                return
        rows = sum(r.n_rows for r in live)
        tracer = self.tracer
        ctxs = ([r.ctx for r in live if r.ctx is not None]
                if tracer is not None and tracer.enabled else [])
        if ctxs:
            # one dispatch span fanning in the N coalesced request
            # spans: each context's flow arrow (opened in its submit
            # span) is closed HERE, so Perfetto draws N arrows into
            # this slice; trace ids ride the span args for grepping
            with tracer.span("dispatch", cat="serving", model=self.name,
                             n_requests=len(live), rows=rows,
                             trace_ids=[c.trace_id for c in ctxs]):
                for c in ctxs:
                    tracer.flow_end("req", c.flow_id, cat="serving")
                self._dispatch_compiled(live, rows)
        else:
            self._dispatch_compiled(live, rows)

    def _dispatch_compiled(self, live: List[_Request], rows: int) -> None:
        try:
            if self._faults is not None:
                # fault site — inside the handler, so an injected
                # dispatch error resolves the group's futures like any
                # real dispatch failure; ReplicaDeathFault is a
                # BaseException and ESCAPES, killing this batcher
                # thread with the group stranded, exactly like a real
                # thread crash (the failure the ReplicaSet supervisor
                # exists to detect)
                ix = self._dispatch_index
                self._dispatch_index += 1
                self._faults.serving_dispatch(ix, self._fault_replica)
            if len(live) == 1:
                x = live[0].x
            else:
                x = _tree.tree_map(
                    lambda *leaves: np.concatenate(leaves, axis=0),
                    *[r.x for r in live])
            bucket = self._bucket_for(rows)
            x = pad_rows(x, bucket)
            out = _tree.tree_map(
                np.asarray,
                self._compiled[bucket](self.params, self.state, x))
            # defense in depth behind the warmup rows-track gate: never
            # slice per-request offsets out of an output whose leading
            # dim is not the dispatched bucket — fail the group loudly
            bad = [o.shape for o in _tree.tree_leaves(out)
                   if o.shape[:1] != (bucket,)]
            if bad:
                raise RuntimeError(
                    f"output leading dims {bad} != bucket {bucket}; "
                    "refusing to slice per-request results")
            self.metrics.record_dispatch(rows, bucket)
            now = time.monotonic()
            off = 0
            for r in live:
                lo, hi = off, off + r.n_rows
                if settle_future(r.future, result=_tree.tree_map(
                        lambda o: o[lo:hi], out)):
                    # counted only when THIS dispatch settled it — a
                    # straggler completing a request the supervisor
                    # already failed over must not double-count it
                    self.metrics.record_done(r.n_rows,
                                             now - r.t_enqueue,
                                             bucket=bucket)
                off = hi
        except Exception as e:  # resolve, never strand, the waiters
            for r in live:
                if not r.future.done():
                    if settle_future(r.future, exc=e):
                        self.metrics.record_failure(r.n_rows)

    # -- stats / lifecycle -------------------------------------------------
    @property
    def alive(self) -> bool:
        """False once the batcher thread has DIED without an orderly
        stop — a crashed dispatch (or an injected ``ReplicaDeathFault``)
        took it down, so accepted work can no longer dispatch.  A parked
        (``start=False``, not yet started) service counts as alive: it
        can still be started.  This is the liveness predicate the
        ``ReplicaSet`` supervisor polls."""
        return not self._stopped and not self._batcher.dead

    def revive(self) -> bool:
        """Replace a DEAD batcher thread with a fresh one over the SAME
        warmed bucket executables — no recompile, params untouched, the
        service keeps its name/metrics.  The dead batcher's stranded
        backlog is cancelled first (its futures are typically already
        failed over by the ``ReplicaSet`` supervisor).  No-op (returns
        False) while the current batcher is healthy; raises
        :class:`ServiceClosed` after :meth:`stop`."""
        with self._lifecycle_lock:
            if self._stopped:
                raise ServiceClosed(
                    f"cannot revive stopped service {self.name!r}")
            if not self._batcher.dead:
                return False
            cancelled = self._batcher.close(drain=False, timeout=1.0)
            if cancelled:
                self.metrics.record_cancel(cancelled)
            self._finalizer.detach()
            self._batcher = self._make_batcher()
            self._finalizer = weakref.finalize(
                self, RequestBatcher.close, self._batcher, True, 5.0)
            self._batcher.start()
            return True

    @property
    def last_progress(self) -> Optional[float]:
        """Monotonic time of the batcher's last completed dispatch (or
        its start; None before either) — the liveness signal the
        ``ReplicaSet`` supervisor uses to tell a WEDGED replica from a
        merely congested one."""
        return self._batcher.last_progress

    def queue_depth(self) -> int:
        return self._batcher.depth()

    def stats(self) -> dict:
        """Snapshot dict — schema documented in README "serving"."""
        snap = self.metrics.snapshot(queue_depth=self._batcher.depth(),
                                     compile_count=self._trace_count)
        snap["model"] = self.name
        snap["max_batch_size"] = self.max_batch_size
        snap["buckets"] = list(self.buckets)
        return snap

    def start(self) -> None:
        with self._lifecycle_lock:
            self._batcher.start()

    def stop(self, drain: bool = True,
             timeout: Optional[float] = None) -> None:
        """Graceful shutdown: refuse new submits, drain (default) or
        cancel the backlog, join the batcher.  Idempotent."""
        with self._lifecycle_lock:
            if self._stopped:
                return
            self._stopped = True
            self._finalizer.detach()
            cancelled_rows = self._batcher.close(drain=drain,
                                                 timeout=timeout)
        if cancelled_rows:
            self.metrics.record_cancel(cancelled_rows)
        # a stopped service must not linger on the admin plane (its
        # metrics would be pinned forever and a redeploy under the
        # same name expects a clean slot)
        if self._admin_name is not None:
            from bigdl_tpu.telemetry import admin as _admin
            _srv = _admin.current()
            if _srv is not None:
                _srv.remove_source(self._admin_name)

    def release(self) -> None:
        """Drop params/state/bucket executables of a STOPPED service so
        a retired replica slot stops pinning device memory until it is
        reused (``ReplicaSet.set_replica_count`` shrink path).  Refuses
        on a live service — the batcher thread still dispatches through
        these references."""
        if not self._stopped:
            raise RuntimeError(
                f"release() on live service {self.name!r}; stop() first")
        self.params = None
        self.state = None
        with self._warm_lock:
            self._compiled = {}
            self._warmed = False
            self._row_spec = None

    def __enter__(self) -> "InferenceService":
        return self

    def __exit__(self, *exc) -> None:
        self.stop(drain=True)
