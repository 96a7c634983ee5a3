"""graftlint: rule unit tests + the tier-1 gate over the real tree.

Layout:
- one positive AND one negative test per rule (acceptance criterion);
- traced-scope model tests (conventions: ``apply`` traced, eager
  ``forward`` not, call-graph reachability, taint laundering);
- suppression scoping (trailing line / standalone-above / file-level);
- CLI exit codes + JSON schema;
- ``--changed-only`` filtering unit;
- THE GATE: ``bigdl_tpu/`` must be violation-free modulo reviewed
  inline suppressions.  This test is what makes graftlint part of
  tier-1 — a PR that introduces a silent-recompile / host-sync /
  impure-forward hazard fails here with rule id + file:line.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.graftlint import (
    JSON_SCHEMA_VERSION,
    all_rules,
    filter_changed,
    lint_paths,
    lint_source,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = "bigdl_tpu/nn/fake.py"  # default lint path: library, traced rules on


def lint(src, path=LIB, **kw):
    return lint_source(textwrap.dedent(src), path=path, **kw)


def rule_ids(src, path=LIB, **kw):
    return sorted({v.rule for v in lint(src, path=path, **kw)})


# ===========================================================================
# GL101 host-sync
# ===========================================================================
class TestHostSync:
    def test_positive_item_float_asarray_device_get(self):
        vs = lint("""
            import jax
            import numpy as np
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    v = input.sum().item()
                    f = float(input.mean())
                    a = np.asarray(input)
                    g = jax.device_get(input)
                    return v + f, state
            """)
        assert [v.rule for v in vs] == ["GL101"] * 4
        assert all(v.severity == "error" for v in vs)

    def test_negative_static_receiver_and_eager_forward(self):
        # np.asarray of a static config table is trace-time constant
        # folding; .item()/float() in the EAGER forward path are fine
        assert rule_ids("""
            import numpy as np
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    tbl = np.asarray(self.conn_table)
                    return input * tbl.sum().item(), state
                def forward(self, x):
                    return float(x.sum())
            """) == []

    def test_positive_reachable_through_helper(self):
        # "reachable from jitted paths": the sync lives in a helper the
        # traced apply calls — the helper's param is tainted via the
        # call site
        vs = lint("""
            def _readout(x):
                return x.max().item()
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    return _readout(input), state
            """)
        assert [(v.rule, "_readout" in v.message) for v in vs] == \
            [("GL101", True)]

    def test_negative_helper_called_with_static_only(self):
        assert rule_ids("""
            import numpy as np
            def _lookup(name):
                return np.asarray(TABLES[name]).item()
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    return input * _lookup(self.kind), state
            """) == []


# ===========================================================================
# GL102 tensor-branch
# ===========================================================================
class TestTensorBranch:
    def test_positive_if_while_assert_on_tensor(self):
        vs = lint("""
            import jax.numpy as jnp
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    if input.sum() > 0:
                        input = -input
                    while jnp.any(input > 0):
                        input = input - 1
                    assert input.mean() < 1
                    return input, state
            """)
        assert [v.rule for v in vs] == ["GL102"] * 3
        msgs = " ".join(v.message for v in vs)
        assert "lax.cond" in msgs and "lax.while_loop" in msgs

    def test_negative_static_branches(self):
        # shape/rank dispatch, hyper-params, rng None-plumbing, dict
        # membership, training flag: all legal trace-time branches
        assert rule_ids("""
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    if input.ndim == 3:
                        input = input[None]
                    if rng is None and self.p > 0:
                        pass
                    if "gamma" in params:
                        input = input * params["gamma"]
                    if training and input.shape[0] > 1:
                        pass
                    return input, state
            """) == []

    def test_positive_optimizer_update(self):
        vs = lint("""
            class Clip(OptimMethod):
                def update(self, grads, params, opt_state, lr, step):
                    if grads["w"].sum() > 1e3:
                        grads = clip(grads)
                    return params, opt_state
            """, path="bigdl_tpu/optim/fake.py")
        assert [v.rule for v in vs] == ["GL102"]

    def test_negative_host_transform_not_traced(self):
        # transform/vision.py-style numpy augmentation: apply on a
        # non-Module class is host-side, branch away
        assert rule_ids("""
            class Brightness(FeatureTransformer):
                def apply(self, img):
                    if img.mean() > 0.5:
                        img = img * 0.9
                    return img
            """, path="bigdl_tpu/transform/fake.py") == []

    def test_positive_jit_decorated_function(self):
        vs = lint("""
            import jax
            @jax.jit
            def step(params, x):
                if x.sum() > 0:
                    return params
                return x
            """, path="bigdl_tpu/optim/fake.py")
        assert [v.rule for v in vs] == ["GL102"]

    def test_positive_lax_combinator_callback(self):
        vs = lint("""
            from jax import lax
            def body(carry):
                if carry > 0:
                    return carry - 1
                return carry
            def run(x):
                return lax.while_loop(lambda c: c != 0, body, x)
            """)
        assert [v.rule for v in vs] == ["GL102"]

    def test_negative_builtin_map_callback_is_host_code(self):
        # builtin map() is host iteration; only lax.map traces
        assert rule_ids("""
            def _fmt(row):
                if row > 0:
                    return "+"
                return "-"
            def report(rows):
                return list(map(_fmt, rows))
            """, path="bigdl_tpu/utils/fake.py") == []

    def test_positive_lax_map_callback_is_traced(self):
        vs = lint("""
            from jax import lax
            def _body(row):
                if row.sum() > 0:
                    return row
                return -row
            def run(xs):
                return lax.map(_body, xs)
            """)
        assert [v.rule for v in vs] == ["GL102"]

    def test_negative_scalar_annotated_config_param(self):
        # `causal: bool` under a shard_map callback is partial-bound
        # static config, not a tracer
        assert rule_ids("""
            from functools import partial
            def _local(q, k, *, causal: bool, axis_name: str):
                if causal:
                    q = q * 2
                return q
            def attn(q, k, mesh):
                return shard_map(partial(_local, causal=True,
                                         axis_name="seq"),
                                 mesh=mesh)(q, k)
            """, path="bigdl_tpu/parallel/fake.py") == []


# ===========================================================================
# GL103 impure-forward
# ===========================================================================
class TestPurity:
    def test_positive_self_mutation_and_global(self):
        vs = lint("""
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    self.output = input * 2
                    self.cache.append(input)
                    global _STEPS
                    _STEPS += 1
                    return input, state
            """)
        assert [v.rule for v in vs] == ["GL103"] * 3

    def test_negative_locals_and_eager_paths(self):
        # local assignment in apply is fine; eager forward/backward
        # write self by design (not traced); __init__ is never traced
        assert rule_ids("""
            class Foo(Module):
                def __init__(self):
                    self.calls = 0
                def forward(self, x):
                    self.output = x
                    return x
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    out = input * 2
                    new_state = {"mean": out.mean()}
                    return out, new_state
            """) == []

    def test_negative_functional_update_call_is_not_a_dict_write(self):
        # composing optimizers: self.inner.update(g, p, s, lr, it) is
        # the 5-arg functional contract, not container mutation
        assert rule_ids("""
            class Wrapped(OptimMethod):
                def update(self, grads, params, opt_state, lr, step):
                    return self.inner.update(grads, params, opt_state,
                                             lr, step)
            """, path="bigdl_tpu/optim/fake.py") == []

    def test_positive_closure_nonlocal(self):
        vs = lint("""
            class Foo(Module):
                def apply(self, params, state, input, *, training=False,
                          rng=None):
                    count = 0
                    def inner(x):
                        nonlocal count
                        count += 1
                        return x
                    return inner(input), state
            """)
        assert [v.rule for v in vs] == ["GL103"]


# ===========================================================================
# GL104 float64-promotion
# ===========================================================================
class TestFloat64:
    def test_positive_np_float64_and_dtype_strings(self):
        vs = lint("""
            import numpy as np
            A = np.zeros(4, dtype=np.float64)
            def f(x):
                return x.astype("float64")
            B = np.ones(3, dtype="float64")
            """)
        assert [v.rule for v in vs] == ["GL104"] * 3

    def test_negative_f32_and_nonlibrary_paths(self):
        assert rule_ids("""
            import numpy as np
            A = np.zeros(4, dtype=np.float32)
            """) == []
        src = "import numpy as np\nA = np.float64(3)\n"
        assert rule_ids(src, path="tests/test_foo.py") == []
        assert rule_ids(src, path="bigdl_tpu/dataset/foo.py") == []
        # interop/ is the wire-format boundary: f64 mandated there
        assert rule_ids(src, path="bigdl_tpu/interop/foo.py") == []


# ===========================================================================
# GL105 nondeterministic-rng
# ===========================================================================
class TestNpRandom:
    def test_positive_global_rng_and_unseeded_generator(self):
        vs = lint("""
            import numpy as np
            def init(shape):
                return np.random.normal(0, 1, shape)
            g = np.random.default_rng()
            np.random.seed(0)
            """)
        assert [v.rule for v in vs] == ["GL105"] * 3

    def test_negative_seeded_and_scoped_paths(self):
        assert rule_ids("""
            import numpy as np
            r = np.random.default_rng(1234)
            s = np.random.SeedSequence(7)
            def gen(seed):
                return np.random.default_rng(seed).normal()
            """) == []
        src = "import numpy as np\nx = np.random.rand(3)\n"
        assert rule_ids(src, path="bigdl_tpu/dataset/mnist.py") == []
        assert rule_ids(src, path="tests/test_foo.py") == []


# ===========================================================================
# GL106 recompile-hazard
# ===========================================================================
class TestRecompile:
    def test_positive_inline_jit_per_call(self):
        vs = lint("""
            import jax
            def train_step(params, x):
                return jax.jit(lambda p, v: p * v)(params, x)
            """)
        assert [v.rule for v in vs] == ["GL106"]
        assert "fresh jit cache" in vs[0].message

    def test_positive_jit_in_loop(self):
        vs = lint("""
            import jax
            def sweep(fns, x):
                outs = []
                for f in fns:
                    outs.append(jax.jit(f))
                return outs
            """)
        assert [v.rule for v in vs] == ["GL106"]
        assert "loop" in vs[0].message

    def test_positive_scalar_literal_without_static_decl(self):
        vs = lint("""
            import jax
            @jax.jit
            def step(params, use_bias):
                return params
            def run(p):
                return step(p, True)
            """)
        assert [v.rule for v in vs] == ["GL106"]
        assert "static_argnums" in vs[0].message

    def test_negative_static_argnames_on_assign_binding(self):
        # static_argnames on a `g = jax.jit(f, ...)` binding must
        # exonerate positional literals via f's param names
        assert rule_ids("""
            import jax
            def step(params, use_bias):
                return params
            fast = jax.jit(step, static_argnames=("use_bias",))
            def run(p):
                return fast(p, True)
            """) == []

    def test_negative_hoisted_and_declared_static(self):
        assert rule_ids("""
            import jax
            from functools import partial
            @partial(jax.jit, static_argnums=(1,))
            def step(params, use_bias):
                return params
            fast = jax.jit(step, static_argnums=(1,))
            def run(p, lr):
                return fast(p, True) + step(p, False) + step(p, lr)
            """) == []


# ===========================================================================
# GL107 driver-loop host sync
# ===========================================================================
OPTIM = "bigdl_tpu/optim/fake.py"


class TestDriverLoopHostSync:
    def test_positive_float_on_step_output_in_while_loop(self):
        vs = lint("""
            import jax
            from functools import partial
            def optimize(params, ostate, batches, done):
                @partial(jax.jit, donate_argnums=(0, 1))
                def train_step(params, ostate, x):
                    return params, ostate, (params * x).sum()
                while not done():
                    x = next(batches)
                    params, ostate, loss = train_step(params, ostate, x)
                    loss = float(loss)
                return params
            """, path=OPTIM)
        assert [v.rule for v in vs] == ["GL107"]
        assert "driver loop" in vs[0].message

    def test_positive_asarray_item_and_jit_assign_binding(self):
        vs = lint("""
            import jax
            import numpy as np
            def _step(p, x):
                return p, x.sum()
            def optimize(p, batches):
                step = jax.jit(_step, donate_argnums=(0,))
                for x in batches:
                    p, loss = step(p, x)
                    a = np.asarray(loss)
                    b = loss.item()
                return p
            """, path=OPTIM)
        assert [v.rule for v in vs] == ["GL107"] * 2

    def test_negative_deferred_one_step_behind_fetch(self):
        # the fix GL107 prescribes: sync the PREVIOUS iteration's value
        # before the dispatch rebinds it — sync-above-producer is clean
        assert rule_ids("""
            import jax
            from functools import partial
            def optimize(params, ostate, batches, done):
                @partial(jax.jit, donate_argnums=(0, 1))
                def train_step(params, ostate, x):
                    return params, ostate, (params * x).sum()
                prev = None
                while not done():
                    if prev is not None:
                        lv = float(prev)
                    params, ostate, prev = train_step(
                        params, ostate, next(batches))
                return params
            """, path=OPTIM) == []

    def test_negative_non_donating_jit_is_an_eval_loop(self):
        # predict/evaluate loops legitimately fetch each batch's output;
        # the donating signature is what marks a TRAINING step
        assert rule_ids("""
            import jax
            import numpy as np
            def evaluate(params, batches):
                fwd = jax.jit(lambda p, x: (p * x).sum())
                outs = []
                for x in batches:
                    out = fwd(params, x)
                    outs.append(np.asarray(out))
                return outs
            """, path=OPTIM) == []

    def test_negative_outside_optim_path(self):
        src = """
            import jax
            from functools import partial
            def drive(p, xs, done):
                @partial(jax.jit, donate_argnums=(0,))
                def step(p, x):
                    return p, x.sum()
                while not done():
                    p, loss = step(p, next(xs))
                    float(loss)
                return p
            """
        assert "GL107" not in rule_ids(src, path="bigdl_tpu/utils/fake.py")
        assert "GL107" not in rule_ids(src, path="tests/test_fake.py")


# ===========================================================================
# GL201 unguarded-shared-state
# ===========================================================================
SERV = "bigdl_tpu/serving/fake.py"


class TestUnguardedSharedState:
    def test_positive_annotated_attr_accessed_outside_lock(self):
        vs = lint("""
            import threading
            class B:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = []   # guarded-by: _cond
                    self._n = 0    # write-guarded-by: _cond
                def bad_read(self):
                    return len(self._q)
                def bad_write(self):
                    self._n = 5
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL201"] * 2
        assert "read of `self._q`" in vs[0].message
        assert "write to `self._n`" in vs[1].message

    def test_negative_locked_access_and_write_guarded_read(self):
        assert rule_ids("""
            import threading
            class B:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = []   # guarded-by: _cond
                    self._n = 0    # write-guarded-by: _cond
                def ok(self):
                    with self._cond:
                        self._q.append(1)
                        self._n += 1
                def ok_read(self):
                    return self._n  # write-guarded: reads lock-free
            """, path=SERV) == []

    def test_negative_held_on_entry_def_annotation(self):
        # the ModelRegistry._resolve contract: caller holds the lock,
        # the def-line annotation makes the body check as locked
        assert rule_ids("""
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._services = {}  # guarded-by: _lock
                # guarded-by: _lock
                def _resolve(self, name):
                    return self._services[name]
                def get(self, name):
                    with self._lock:
                        return self._resolve(name)
            """, path=SERV) == []

    def test_negative_condition_aliasing_counts_as_the_lock(self):
        # Condition(self._lock) IS self._lock (the ReplicaSet._wake
        # shape): holding either guards attrs declared on the lock
        assert rule_ids("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._wake = threading.Condition(self._lock)
                    self._inflight = {}  # guarded-by: _lock
                def a(self):
                    with self._wake:
                        self._inflight.clear()
                def b(self):
                    with self._lock:
                        return len(self._inflight)
            """, path=SERV) == []

    def test_positive_heuristic_cross_thread_write_without_lock(self):
        vs = lint("""
            import threading
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = None
                def start(self):
                    self.value = 0
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)
                    self._t.start()
                def _run(self):
                    self.value = 1
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL201"]
        assert "spawned thread" in vs[0].message

    def test_negative_heuristic_common_lock_on_both_writes(self):
        assert rule_ids("""
            import threading
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = None
                def start(self):
                    with self._lock:
                        self.value = 0
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)
                    self._t.start()
                def _run(self):
                    with self._lock:
                        self.value = 1
            """, path=SERV) == []

    def test_positive_module_global_write_guard(self):
        vs = lint("""
            import threading
            _install_lock = threading.Lock()
            # write-guarded-by: _install_lock
            _installed = None
            def install(x):
                global _installed
                _installed = x
            def current():
                return _installed
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL201"]
        assert vs[0].message.startswith("write to `_installed`")

    def test_negative_local_shadow_of_guarded_global(self):
        # review regression: a function-local variable (or parameter)
        # that shadows an annotated module global is NOT the global —
        # Python scoping makes every occurrence local
        assert rule_ids("""
            import threading
            _install_lock = threading.Lock()
            # write-guarded-by: _install_lock
            _installed = None
            def probe():
                _installed = object()
                return _installed
            def probe2(_installed):
                _installed = None
                return _installed
            def real_write(x):
                global _installed
                with _install_lock:
                    _installed = x
            """, path=SERV) == []

    def test_negative_tests_are_out_of_scope(self):
        src = """
            import threading
            class B:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = []   # guarded-by: _cond
                def bad(self):
                    return len(self._q)
            """
        assert rule_ids(src, path="tests/test_fake.py") == []


# ===========================================================================
# GL202 lock-retake / lock-ordering
# ===========================================================================
class TestLockRetake:
    def test_positive_retake_via_method_call(self):
        # the ModelRegistry._resolve deadlock class: an error path under
        # the lock calls a helper that re-takes the same Lock
        vs = lint("""
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._services = {}
                def get(self, name):
                    with self._lock:
                        if name not in self._services:
                            raise KeyError(self.list_models())
                        return self._services[name]
                def list_models(self):
                    with self._lock:
                        return sorted(self._services)
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL202"]
        assert "list_models" in vs[0].message
        assert "re-take" in vs[0].message

    def test_positive_direct_nested_with_same_lock(self):
        vs = lint("""
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                def f(self):
                    with self._lock:
                        with self._lock:
                            pass
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL202"]

    def test_negative_rlock_and_default_condition_are_reentrant(self):
        assert rule_ids("""
            import threading
            class R:
                def __init__(self):
                    self._rlock = threading.RLock()
                    self._cond = threading.Condition()
                def f(self):
                    with self._rlock:
                        with self._rlock:
                            pass
                def g(self):
                    with self._cond:
                        self.h()
                def h(self):
                    with self._cond:
                        pass
            """, path=SERV) == []

    def test_positive_inconsistent_lock_order(self):
        vs = lint("""
            import threading
            class T:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._b:
                        with self._a:
                            pass
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL202"]
        assert "inconsistent lock order" in vs[0].message

    def test_negative_consistent_two_lock_order(self):
        assert rule_ids("""
            import threading
            class T:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def f(self):
                    with self._a:
                        with self._b:
                            pass
                def g(self):
                    with self._a:
                        with self._b:
                            pass
            """, path=SERV) == []

    def test_positive_held_on_entry_method_called_without_lock(self):
        vs = lint("""
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0
                # guarded-by: _lock
                def _mutate_locked(self):
                    self._n += 1
                def bad(self):
                    self._mutate_locked()
                def good(self):
                    with self._lock:
                        self._mutate_locked()
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL202"]
        assert "held on entry" in vs[0].message


# ===========================================================================
# GL203 future-settlement
# ===========================================================================
class TestFutureSettlement:
    def test_positive_popped_request_never_settled(self):
        # the settle-every-path class: a backlog sweep that pops
        # requests but resolves nothing strands every waiter
        vs = lint("""
            import threading
            from collections import deque
            class B:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = deque()
                def _cancel_backlog(self):
                    rows = 0
                    while True:
                        with self._cond:
                            if not self._q:
                                return rows
                            req = self._q.popleft()
                        rows += req.n_rows
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL203"]
        assert "never settled" in vs[0].message

    def test_negative_cancel_counts_as_settlement(self):
        assert rule_ids("""
            import threading
            from collections import deque
            class B:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = deque()
                def _cancel_backlog(self):
                    rows = 0
                    while True:
                        with self._cond:
                            if not self._q:
                                return rows
                            req = self._q.popleft()
                        if req.future.cancel():
                            rows += req.n_rows
            """, path=SERV) == []

    def test_positive_bare_pop_statement_discards(self):
        vs = lint("""
            class B:
                def drain(self, out_q):
                    out_q.get_nowait()
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL203"]
        assert "discarded" in vs[0].message

    def test_negative_handoff_and_settle_paths(self):
        # append to a batch (hand-off), settle_future(...), unpack then
        # invoke (the AsyncSnapshotWriter shape), subexpression pops
        assert rule_ids("""
            from collections import deque
            def collect(q, dispatch_fn):
                batch = []
                first = q.popleft()
                batch.append(first)
                dispatch_fn(batch)
            def on_done(inflight, token):
                entry = inflight.pop(token, None)
                route, inner = entry
                settle_future(inner, result=1)
            def writer_loop(job_q):
                item = job_q.get()
                job, context = item
                job()
            def drain_results(inflight):
                return [inflight.pop(0).result() for _ in range(3)]
            """, path=SERV) == []

    def test_negative_dict_get_lookup_is_not_a_pop(self):
        assert rule_ids("""
            def route(inflight, token):
                entry = inflight.get(token)
                return entry
            """, path=SERV) == []


# ===========================================================================
# GL204 thread-lifecycle
# ===========================================================================
class TestThreadLifecycle:
    def test_positive_nondaemon_never_joined(self):
        vs = lint("""
            import threading
            def spawn(fn):
                t = threading.Thread(target=fn)
                t.start()
                return t
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL204"]
        assert "neither daemon" in vs[0].message

    def test_positive_unbound_thread_discarded(self):
        vs = lint("""
            import threading
            def fire_and_forget(fn):
                threading.Thread(target=fn, daemon=True).start()
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL204"]
        assert "never bound" in vs[0].message

    def test_negative_daemon_bound_and_joined_variants(self):
        assert rule_ids("""
            import threading
            class S:
                def start(self):
                    self._thread = threading.Thread(target=self._run,
                                                    daemon=True)
                    self._thread.start()
                def stop(self):
                    self._thread.join(timeout=2.0)
                def _run(self):
                    pass
            def run_once(fn):
                t = threading.Thread(target=fn)
                t.start()
                t.join()
            """, path=SERV) == []

    def test_positive_join_in_another_class_does_not_exonerate(self):
        # review regression: the joined/daemon search is scoped to the
        # binding's own class — a same-named `self._thread` joined in
        # a DIFFERENT class must not mask this class's orphan
        vs = lint("""
            import threading
            class Joins:
                def start(self):
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()
                def stop(self):
                    self._thread.join()
                def _run(self):
                    pass
            class Orphans:
                def start(self):
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()
                def _run(self):
                    pass
            """, path=SERV)
        # exactly one finding, anchored inside the non-joining class
        assert [v.rule for v in vs] == ["GL204"]
        assert vs[0].line > 10

    def test_negative_listcomp_bound_threads_joined_via_loop(self):
        # a sweep's shape: a pool of workers joined through
        # iteration over the container binding
        assert rule_ids("""
            import threading
            def sweep(fns):
                workers = [threading.Thread(target=f) for f in fns]
                for t in workers:
                    t.start()
                for t in workers:
                    t.join()
            """, path=SERV) == []


# ===========================================================================
# GL205 wait-predicate
# ===========================================================================
class TestWaitPredicate:
    def test_positive_wait_under_if(self):
        vs = lint("""
            import threading
            class P:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False
                def bad(self):
                    with self._cond:
                        if not self.ready:
                            self._cond.wait()
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL205"]
        assert "while" in vs[0].message

    def test_negative_wait_in_while_loop(self):
        assert rule_ids("""
            import threading
            class P:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False
                def good(self):
                    with self._cond:
                        while not self.ready:
                            self._cond.wait()
                def supervise(self):
                    while True:
                        with self._cond:
                            self._cond.wait(timeout=1.0)
            """, path=SERV) == []

    def test_negative_event_wait_is_not_a_condition(self):
        assert rule_ids("""
            import threading
            def waiter(stop_event):
                stop_event.wait(0.5)
            """, path=SERV) == []


# ===========================================================================
# GL206 blocking-under-lock
# ===========================================================================
class TestBlockingUnderLock:
    def test_positive_sleep_result_fsync_under_lock(self):
        vs = lint("""
            import os
            import threading
            import time
            class D:
                def __init__(self):
                    self._lock = threading.Lock()
                def bad(self, fut, fd):
                    with self._lock:
                        time.sleep(0.1)
                        out = fut.result()
                        os.fsync(fd)
                    return out
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL206"] * 3

    def test_positive_wait_on_foreign_condition_under_lock(self):
        vs = lint("""
            import threading
            class D:
                def __init__(self):
                    self._a = threading.Lock()
                    self._c = threading.Condition()
                def cross(self):
                    with self._a:
                        with self._c:
                            pass
                def bad(self):
                    with self._a:
                        while True:
                            self._c.wait()
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL206"]
        assert "waiting on `self._c`" in vs[0].message

    def test_negative_wait_on_held_condition_releases_it(self):
        assert rule_ids("""
            import threading
            class D:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False
                def ok(self):
                    with self._cond:
                        while not self.ready:
                            self._cond.wait()
            """, path=SERV) == []

    def test_negative_blocking_outside_lock_and_re_compile(self):
        assert rule_ids("""
            import re
            import threading
            import time
            class D:
                def __init__(self):
                    self._lock = threading.Lock()
                def ok(self, fut):
                    with self._lock:
                        pat = re.compile("x+")
                    time.sleep(0.1)
                    return fut.result(), pat
            """, path=SERV) == []

    def test_positive_xla_compile_under_lock(self):
        vs = lint("""
            import threading
            class S:
                def __init__(self, jit):
                    self._warm_lock = threading.Lock()
                    self._jit = jit
                    self._compiled = {}
                def warmup(self, params, spec):
                    with self._warm_lock:
                        self._compiled[1] = self._jit.lower(
                            params, spec).compile()
            """, path=SERV)
        assert [v.rule for v in vs] == ["GL206"]
        assert "XLA compile" in vs[0].message


# ===========================================================================
# GL2xx suppressions + reverted-hazard regression fixtures
# ===========================================================================
class TestGL2Suppressions:
    def test_trailing_suppression_scopes_to_line(self):
        src = ("import threading\n"
               "class B:\n"
               "    def __init__(self):\n"
               "        self._cond = threading.Condition()\n"
               "        self._q = []   # guarded-by: _cond\n"
               "    def racy_hint(self):\n"
               "        return len(self._q)  # graftlint: disable=GL201\n"
               "    def still_bad(self):\n"
               "        return len(self._q)\n")
        vs = lint_source(src, path=SERV)
        assert [(v.rule, v.line) for v in vs] == [("GL201", 9)]

    def test_rule_name_alias_suppresses(self):
        src = ("import threading\n"
               "def fire(fn):\n"
               "    # supervised externally"
               "  graftlint: disable=thread-lifecycle\n"
               "    threading.Thread(target=fn, daemon=True).start()\n")
        assert lint_source(src, path=SERV) == []


class TestRevertedHazards:
    """The acceptance gate: real concurrency-bug classes from the PR
    5/10/11 review rounds, re-created by reverting their fixes in
    fixture form, must be caught by the family."""

    def test_resolve_lock_retake_revert_is_caught(self):
        # PR 5 review: ModelRegistry._resolve's KeyError path re-took
        # the non-reentrant registry lock through a helper — deadlock.
        # The fix documented the caller-must-hold contract; reverting
        # it (helper re-acquires) must fire GL202.
        src = """
            import threading
            class ModelRegistry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._services = {}
                    self._latest = {}
                # guarded-by: _lock
                def _resolve(self, name, version):
                    if name not in self._latest:
                        raise KeyError(
                            f"no model; have {self.list_models()}")
                    return (name, self._latest[name])
                def list_models(self):
                    with self._lock:
                        return sorted(self._services)
                def get(self, name, version=None):
                    with self._lock:
                        return self._services[
                            self._resolve(name, version)]
            """
        vs = lint(src, path="bigdl_tpu/serving/registry_reverted.py")
        assert [v.rule for v in vs] == ["GL202"]
        assert "deadlock" in vs[0].message

    def test_settle_every_path_revert_is_caught(self):
        # PR 5/10 invariant "accepted requests ALWAYS resolve": the
        # batcher's cancel path settles every popped future.  Reverting
        # the settle (pop-and-count only) must fire GL203.
        src = """
            import threading
            from collections import deque
            class RequestBatcher:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = deque()
                    self.cancelled_rows = 0
                def _cancel_backlog(self):
                    rows = 0
                    while True:
                        with self._cond:
                            if not self._q:
                                self.cancelled_rows += rows
                                return rows
                            req = self._q.popleft()
                        rows += req.n_rows
            """
        vs = lint(src, path="bigdl_tpu/serving/batcher_reverted.py")
        assert [v.rule for v in vs] == ["GL203"]

    def test_fixed_shapes_stay_silent(self):
        # the shipped fixes of both classes lint clean — the rules
        # gate the regression, not the idiom
        assert rule_ids("""
            import threading
            from collections import deque
            class RequestBatcher:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = deque()
                    self.cancelled_rows = 0
                def _cancel_backlog(self):
                    rows = 0
                    while True:
                        with self._cond:
                            if not self._q:
                                self.cancelled_rows += rows
                                return rows
                            req = self._q.popleft()
                        if req.future.cancel():
                            rows += req.n_rows
            """, path="bigdl_tpu/serving/batcher_fixed.py") == []

    # -- ISSUE 15: the PR-14 review-round-4 classes, reverted on the
    # -- REAL source (string surgery, then lint) — the strongest gate:
    # -- annotation drift that would blind the rule fails here too
    def test_pin_leak_revert_on_real_server_is_caught(self):
        src = open(os.path.join(REPO, "bigdl_tpu", "frontend",
                                "server.py")).read()
        guarded = ("                try:  # pin held: EVERY exit path "
                   "below must unpin\n"
                   "                    max_batch = "
                   "self._backend_max_batch(backend)")
        reverted = ("                max_batch = "
                    "self._backend_max_batch(backend)\n"
                    "                try:  # pin held: EVERY exit path "
                    "below must unpin")
        assert guarded in src, "server.py pin/try shape moved — " \
            "update this surgery (and keep the pin inside the try)"
        vs = lint_source(src.replace(guarded, reverted),
                         path="bigdl_tpu/frontend/server.py")
        assert "GL301" in {v.rule for v in vs}
        (v,) = [v for v in vs if v.rule == "GL301"]
        assert "wire_inflight" in v.message

    def test_blanket_400_revert_on_real_classify_is_caught(self):
        src = open(os.path.join(REPO, "bigdl_tpu", "frontend",
                                "server.py")).read()
        tail = '        return 500, {"error": f"{type(e).__name__}: ' \
               '{e}"}, {}'
        assert tail in src, "server.py _classify tail moved — " \
            "update this surgery"
        reverted = ('        if isinstance(e, (ValueError, TypeError)):\n'
                    '            return 400, {"error": str(e)}, {}\n'
                    + tail)
        vs = lint_source(src.replace(tail, reverted),
                         path="bigdl_tpu/frontend/server.py")
        assert "GL302" in {v.rule for v in vs}
        (v,) = [v for v in vs if v.rule == "GL302"]
        assert "ValueError" in v.message


# ===========================================================================
# GL301 leaked-acquire
# ===========================================================================
_PIN_PRELUDE = """
    import threading
    class _WireInflight:
        def __init__(self):
            self._cond = threading.Condition()
            self._counts = {}
        def enter(self, key):  # acquires: wire_inflight
            with self._cond:
                self._counts[key] = self._counts.get(key, 0) + 1  # acquires: wire_inflight
        def exit(self, key):  # releases: wire_inflight
            with self._cond:
                self._counts.pop(key, None)  # releases: wire_inflight
"""


class TestLeakedAcquire:
    def test_positive_statement_between_acquire_and_try(self):
        # the PR-14 shape: one fallible statement between the pin and
        # its try/finally leaks the pin on a raise
        vs = lint(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            key = (name, version)
            self.inflight.enter(key)
            return key, self.backend
        def _run_predict(self, name, version, x):
            key, backend = self._resolve_pinned(name, version)
            max_batch = int(backend.max_batch_size)
            try:
                return self._predict(backend, x, max_batch)
            finally:
                self.inflight.exit(key)
            """, path="bigdl_tpu/frontend/server_fx.py")
        assert [v.rule for v in vs] == ["GL301"]
        assert "wire_inflight" in vs[0].message

    def test_negative_next_statement_try_finally_release(self):
        assert rule_ids(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            key = (name, version)
            self.inflight.enter(key)
            return key, self.backend
        def _run_predict(self, name, version, x):
            key, backend = self._resolve_pinned(name, version)
            try:
                max_batch = int(backend.max_batch_size)
                return self._predict(backend, x, max_batch)
            finally:
                self.inflight.exit(key)
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_negative_acquire_inside_protected_try(self):
        # acquiring INSIDE a try whose finally releases is also safe
        # (the release tolerates a never-completed acquire)
        assert rule_ids(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            key = (name, version)
            self.inflight.enter(key)
            return key, self.backend
        def _run_predict(self, name, version, x):
            key = (name, version)
            backend = None
            try:
                key, backend = self._resolve_pinned(name, version)
                return self._predict(backend, x)
            finally:
                self.inflight.exit(key)
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_negative_ownership_transfer_def_annotation(self):
        # a caller that is ITSELF `# acquires:`-annotated passes the
        # obligation up — its own body is exempt for that resource
        assert rule_ids(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            key = (name, version)
            self.inflight.enter(key)
            if self.registry is None:
                raise KeyError(name)
            return key, self.backend
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_positive_unprotected_call_in_loop_body(self):
        vs = lint(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            self.inflight.enter((name, version))
            return (name, version)
        def drain_all(self, names):
            for n in names:
                key = self._resolve_pinned(n, None)
                self.log(key)
            """, path="bigdl_tpu/frontend/server_fx.py")
        assert [v.rule for v in vs] == ["GL301"]

    def test_negative_tests_are_out_of_scope(self):
        assert rule_ids(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            self.inflight.enter((name, version))
            return (name, version)
        def use(self):
            k = self._resolve_pinned("m", 1)
            self.log(k)
            """, path="tests/test_server_fx.py") == []

    def test_positive_acquire_inside_match_case_body(self):
        # review regression: match/case bodies are blocks too — an
        # unprotected acquire inside one must not pass silently
        vs = lint(_PIN_PRELUDE + """
    class Server:
        # acquires: wire_inflight
        def _resolve_pinned(self, name, version):
            self.inflight.enter((name, version))
            return (name, version)
        def route(self, kind, name):
            match kind:
                case "predict":
                    key = self._resolve_pinned(name, None)
                    self.log(key)
                case _:
                    pass
            """, path="bigdl_tpu/frontend/server_fx.py")
        assert [v.rule for v in vs] == ["GL301"]


# ===========================================================================
# GL302 error-taxonomy
# ===========================================================================
class TestErrorTaxonomy:
    def test_positive_blanket_except_feeding_400(self):
        vs = lint("""
            class Handler:
                def parse(self, body):
                    try:
                        return self.decode(body)
                    except Exception as e:
                        raise _HTTPError(400, f"bad body: {e}")
            """, path="bigdl_tpu/frontend/server_fx.py")
        assert [v.rule for v in vs] == ["GL302"]
        assert "blanket" in vs[0].message

    def test_positive_isinstance_classifier_on_undeclared_type(self):
        # THE PR-14 bug: blanket ValueError/TypeError -> 400 in the
        # status classifier hides internal bugs from the 5xx SLO
        vs = lint("""
            class Server:
                @staticmethod
                def _classify(e):
                    if isinstance(e, (ValueError, TypeError)):
                        return 400, {"error": str(e)}, {}
                    return 500, {"error": str(e)}, {}
            """, path="bigdl_tpu/frontend/server_fx.py")
        assert [v.rule for v in vs] == ["GL302"]
        assert "ValueError" in vs[0].message

    def test_negative_declared_types_may_map_4xx(self):
        assert rule_ids("""
            class Server:
                @staticmethod
                def _classify(e):
                    if isinstance(e, _HTTPError):
                        return e.status, e.body, e.headers
                    if isinstance(e, UnknownTenantError):
                        return 403, {"error": str(e)}, {}
                    if isinstance(e, RequestSpecError):
                        return 400, {"error": str(e)}, {}
                    return 500, {"error": str(e)}, {}
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_negative_narrow_typed_wrap_at_origin_is_blessed(self):
        # individually-wrapped client-input parse sites (the round-4
        # fix pattern) stay silent: the caught type is SPECIFIC to the
        # guarded operation
        assert rule_ids("""
            class Handler:
                def parse_len(self, headers):
                    try:
                        return int(headers.get("Content-Length", -1))
                    except ValueError:
                        raise _HTTPError(400, "bad Content-Length")
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_negative_5xx_from_blanket_except_is_fine(self):
        # mapping unknown errors to 500 is the CORRECT taxonomy
        assert rule_ids("""
            class Handler:
                def run(self, body):
                    try:
                        return self.dispatch(body)
                    except Exception as e:
                        self.send_json(500, {"error": str(e)})
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_negative_outside_wire_plane(self):
        # GL302 is scoped to frontend/ + serving/: HTTP statuses mean
        # nothing elsewhere
        assert rule_ids("""
            class Thing:
                def classify(self, e):
                    if isinstance(e, ValueError):
                        return 400
                    return 500
            """, path="bigdl_tpu/optim/thing_fx.py") == []

    def test_file_client_error_declaration_extends_taxonomy(self):
        assert rule_ids("""
            # graftlint: client-error=MyParseError
            class Server:
                @staticmethod
                def _classify(e):
                    if isinstance(e, MyParseError):
                        return 400, {"error": str(e)}, {}
                    return 500, {"error": str(e)}, {}
            """, path="bigdl_tpu/frontend/server_fx.py") == []

    def test_positive_bare_except_sending_4xx(self):
        vs = lint("""
            class Handler:
                def go(self, req):
                    try:
                        self.handle(req)
                    except:
                        self.send_json(404, {"error": "nope"})
            """, path="bigdl_tpu/serving/handler_fx.py")
        assert [v.rule for v in vs] == ["GL302"]


# ===========================================================================
# GL303 release-on-all-paths
# ===========================================================================
class TestReleaseOnAllPaths:
    def test_positive_one_way_counter(self):
        vs = lint("""
            import threading
            class Health:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._probe_inflight = False
                def admit(self):
                    with self._lock:
                        self._probe_inflight = True  # acquires: probe_slot
                        return "probe"
            """, path="bigdl_tpu/resilience/health_fx.py")
        assert [v.rule for v in vs] == ["GL303"]
        assert "probe_slot" in vs[0].message

    def test_positive_unannotated_mutation_of_tracked_counter(self):
        # a new inc/dec added outside the discipline — the PR-10
        # probe-slot leak entered exactly this way
        vs = lint("""
            import threading
            class Health:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._probe_inflight = False
                def admit(self):
                    with self._lock:
                        self._probe_inflight = True  # acquires: probe_slot
                        return "probe"
                def cancel_probe(self):
                    with self._lock:
                        self._probe_inflight = False  # releases: probe_slot
                def sneaky_reset(self):
                    self._probe_inflight = False
            """, path="bigdl_tpu/resilience/health_fx.py")
        assert [v.rule for v in vs] == ["GL303"]
        assert "sneaky" not in vs[0].message  # message names the attr
        assert "_probe_inflight" in vs[0].message

    def test_negative_paired_and_fully_annotated(self):
        assert rule_ids("""
            import threading
            class Batcher:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q_rows = 0
                def put(self, req):
                    with self._cond:
                        self._q_rows += req.n_rows  # acquires: queue_rows
                def pop(self, req):
                    with self._cond:
                        self._q_rows -= req.n_rows  # releases: queue_rows
            """, path="bigdl_tpu/serving/batcher_fx.py") == []

    def test_negative_init_mutation_exempt(self):
        # construction happens-before sharing: the __init__ zero needs
        # no annotation (same exemption as GL201)
        assert rule_ids("""
            import threading
            class Counts:
                def __init__(self):
                    self._n = 0
                def inc(self):
                    self._n += 1  # acquires: slots
                def dec(self):
                    self._n -= 1  # releases: slots
            """, path="bigdl_tpu/serving/counts_fx.py") == []

    def test_negative_unannotated_files_are_silent(self):
        # the rule is annotation-driven: no annotations, no opinions
        assert rule_ids("""
            class Plain:
                def bump(self):
                    self._n += 1
            """, path="bigdl_tpu/serving/plain_fx.py") == []


# ===========================================================================
# GL401 divergent-collective
# ===========================================================================
class TestDivergentCollective:
    def test_positive_process_index_branch(self):
        vs = lint("""
            import jax
            from jax.experimental import multihost_utils
            def maybe_sync(tag):
                if jax.process_index() == 0:
                    multihost_utils.sync_global_devices(tag)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL401"]
        assert "one-sided" in vs[0].message

    def test_positive_tainted_name_predicate(self):
        # taint flows through the assignment: rank IS process-local
        vs = lint("""
            import jax
            from jax.experimental import multihost_utils
            def gather(arr):
                rank = jax.process_index()
                if rank == 0:
                    return multihost_utils.process_allgather(arr)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL401"]

    def test_positive_filesystem_predicate(self):
        # the filesystem is per-host: an exists() gate diverges
        vs = lint("""
            import os
            from jax.experimental import multihost_utils
            def gather(arr, path):
                if os.path.exists(path):
                    return multihost_utils.process_allgather(arr)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL401"]

    def test_positive_collective_reached_through_helper(self):
        # same-file closure: the branch calls a helper that collects
        vs = lint("""
            import time
            from jax.experimental import multihost_utils
            def _sync(arr):
                return multihost_utils.process_allgather(arr)
            def gather(arr, deadline):
                if time.monotonic() > deadline:
                    return _sync(arr)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL401"]

    def test_positive_ifexp_arm(self):
        vs = lint("""
            import time
            from jax.experimental import multihost_utils
            def gather(arr, t0):
                return (multihost_utils.process_allgather(arr)
                        if time.monotonic() > t0 else arr)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL401"]
        assert "both arms" in vs[0].message

    def test_negative_uniform_predicate(self):
        # process_count is the same value on every process
        assert rule_ids("""
            import jax
            from jax.experimental import multihost_utils
            def gather(arr):
                if jax.process_count() > 1:
                    return multihost_utils.process_allgather(arr)
                return arr
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []

    def test_negative_replicated_by_on_branch(self):
        assert rule_ids("""
            import os
            from jax.experimental import multihost_utils
            def gather(arr, path):
                # the flag file is written by the membership ledger on
                # every host at the same epoch
                # replicated-by: membership-epoch-ledger
                if os.path.exists(path):
                    return multihost_utils.process_allgather(arr)
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []

    def test_negative_replicated_by_on_predicate_assignment(self):
        # annotating the assignment that PRODUCES the predicate clears
        # the taint at its source
        assert rule_ids("""
            import os
            from jax.experimental import multihost_utils
            def gather(arr, path):
                armed = os.path.exists(path)  # replicated-by: config-derived
                if armed:
                    return multihost_utils.process_allgather(arr)
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []

    def test_negative_tests_and_datasets_exempt(self):
        src = """
            import jax
            from jax.experimental import multihost_utils
            def maybe_sync(tag):
                if jax.process_index() == 0:
                    multihost_utils.sync_global_devices(tag)
            """
        assert rule_ids(src, path="tests/fake_spmd.py") == []
        assert rule_ids(src, path="bigdl_tpu/dataset/fake_spmd.py") == []


# ===========================================================================
# GL402 world-size-dependent-state
# ===========================================================================
class TestWorldSizeDependentState:
    def test_positive_schema_without_bucket_content(self):
        vs = lint("""
            def checkpoint_schema(plan):
                return build_schema(n_shard=8,
                                    bucket_sizes=plan.sizes)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL402"]
        assert "bucket_content" in vs[0].message

    def test_positive_world_size_into_persisted_state(self):
        vs = lint("""
            import jax
            def snapshot(state):
                state["world"] = jax.process_count()
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL402"]
        assert "reshard_state" in vs[0].message

    def test_negative_schema_with_bucket_content(self):
        assert rule_ids("""
            def checkpoint_schema(plan):
                return build_schema(n_shard=8,
                                    bucket_sizes=plan.sizes,
                                    bucket_content=plan.content)
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []

    def test_negative_reshard_path_exempts_the_function(self):
        assert rule_ids("""
            import jax
            def adopt(state, leaves, plan):
                state["world"] = jax.process_count()
                return reshard_state(leaves, plan)
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []


# ===========================================================================
# GL403 replay-boundary-violation
# ===========================================================================
class TestReplayBoundaryViolation:
    def test_positive_fetch_outside_boundary(self):
        vs = lint("""
            import jax
            def peek_loss(losses):
                return jax.device_get(losses)
            """, path="bigdl_tpu/optim/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL403"]
        assert "replay boundary" in vs[0].message

    def test_positive_restore_outside_boundary(self):
        vs = lint("""
            def hot_reload(mgr, target, ckpt):
                return mgr.restore_into(target, ckpt)
            """, path="bigdl_tpu/resilience/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL403"]

    def test_negative_annotated_boundary_def(self):
        assert rule_ids("""
            import jax
            # replay-boundary: callers reach this only at block edges
            def capture(losses):
                return jax.device_get(losses)
            """, path="bigdl_tpu/optim/fake_spmd.py") == []

    def test_negative_nested_def_inherits_boundary(self):
        # the ancestor chain carries the boundary: a closure inside a
        # boundary def needs no annotation of its own
        assert rule_ids("""
            import jax
            # replay-boundary: block edge
            def replay(losses):
                def fetch():
                    return jax.device_get(losses)
                return fetch()
            """, path="bigdl_tpu/optim/fake_spmd.py") == []

    def test_negative_outside_replay_planes(self):
        # serving fetches freely: the rule's blast radius is the
        # optim/checkpoint/resilience planes
        assert rule_ids("""
            import jax
            def predict(out):
                return jax.device_get(out)
            """, path="bigdl_tpu/serving/fake_spmd.py") == []


# ===========================================================================
# GL404 collective-in-divergent-loop
# ===========================================================================
class TestCollectiveInDivergentLoop:
    def test_positive_unguarded_share_feeds_fast_forward(self):
        vs = lint("""
            def resume(records, scale, it):
                skip = records // scale
                return fast_forward_records(it, skip)
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL404"]
        assert "divisibility" in vs[0].message

    def test_positive_floored_trip_count_over_collective(self):
        vs = lint("""
            import jax
            def drain(total, hosts, xs):
                steps = total // hosts
                for _ in range(steps):
                    xs = jax.lax.psum(xs, "data")
                return xs
            """, path="bigdl_tpu/parallel/fake_spmd.py")
        assert [v.rule for v in vs] == ["GL404"]
        assert "trip count" in vs[0].message

    def test_negative_guarded_by_raise(self):
        assert rule_ids("""
            def resume(records, scale, it):
                if records % scale:
                    raise ValueError("indivisible mid-epoch counter")
                skip = records // scale
                return fast_forward_records(it, skip)
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []

    def test_negative_guarded_by_assert(self):
        assert rule_ids("""
            import jax
            def drain(total, hosts, xs):
                assert total % hosts == 0
                steps = total // hosts
                for _ in range(steps):
                    xs = jax.lax.psum(xs, "data")
                return xs
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []

    def test_negative_loop_without_collectives(self):
        assert rule_ids("""
            def chunk(total, hosts, xs):
                n = total // hosts
                out = []
                for i in range(n):
                    out.append(xs[i])
                return out
            """, path="bigdl_tpu/parallel/fake_spmd.py") == []


# ===========================================================================
# the `# replicated-by:` mechanism ledger (cross-file contract)
# ===========================================================================
class TestMechanismLedger:
    def _model(self, src, path):
        import ast as _ast
        from tools.graftlint import spmd
        src = textwrap.dedent(src)
        return spmd.SpmdModel(_ast.parse(src), src, path)

    def test_mirror_use_without_provider_is_reported(self):
        from tools.graftlint import spmd
        user = self._model("""
            from jax.experimental import multihost_utils
            def dedup(mgr, step, arr):
                # replicated-by: step-mirror
                if mgr.last_saved_step != step:
                    multihost_utils.sync_global_devices("save")
            """, "bigdl_tpu/optim/user.py")
        got = spmd.mechanism_ledger([user])
        assert [(p, m) for p, _ln, m in got] == [
            ("bigdl_tpu/optim/user.py", "step-mirror")]

    def test_provider_in_another_file_satisfies_the_use(self):
        from tools.graftlint import spmd
        user = self._model("""
            from jax.experimental import multihost_utils
            def dedup(mgr, step, arr):
                # replicated-by: step-mirror
                if mgr.last_saved_step != step:
                    multihost_utils.sync_global_devices("save")
            """, "bigdl_tpu/optim/user.py")
        provider = self._model("""
            def save(mgr, step):
                mgr.last_saved_step = step  # replicates: step-mirror
            """, "bigdl_tpu/checkpoint/provider.py")
        assert spmd.mechanism_ledger([user, provider]) == []

    def test_non_mirror_mechanisms_need_no_provider(self):
        from tools.graftlint import spmd
        user = self._model("""
            from jax.experimental import multihost_utils
            def gather(cfg, arr):
                # replicated-by: config-derived
                if cfg.multi_host:
                    multihost_utils.process_allgather(arr)
            """, "bigdl_tpu/optim/user.py")
        assert spmd.mechanism_ledger([user]) == []

    def test_real_tree_ledger_is_satisfied(self):
        # the shipped sources carry exactly the providers their
        # `*-mirror` uses demand
        import ast as _ast
        from tools.graftlint import spmd
        models = []
        for rel in ("bigdl_tpu/optim/optimizer.py",
                    "bigdl_tpu/optim/distri_optimizer.py"):
            src = open(os.path.join(REPO, rel)).read()
            models.append(spmd.SpmdModel(_ast.parse(src), src, rel))
        assert spmd.mechanism_ledger(models) == []

    def test_deleting_the_real_mirror_write_fails_the_ledger(self):
        # cross-file gate: the provider lives in distri_optimizer.py,
        # the uses in optimizer.py — deleting the provider annotation
        # (as a refactor dropping the mirror write would) must surface
        # at the USE sites
        import ast as _ast
        from tools.graftlint import spmd
        osrc = open(os.path.join(REPO, "bigdl_tpu", "optim",
                                 "optimizer.py")).read()
        dsrc = open(os.path.join(REPO, "bigdl_tpu", "optim",
                                 "distri_optimizer.py")).read()
        assert "# replicates: checkpoint-step-mirror" in dsrc, \
            "mirror-write provider annotation moved — update this test"
        dsrc = dsrc.replace("# replicates: checkpoint-step-mirror", "#")
        models = [
            spmd.SpmdModel(_ast.parse(osrc), osrc,
                           "bigdl_tpu/optim/optimizer.py"),
            spmd.SpmdModel(_ast.parse(dsrc), dsrc,
                           "bigdl_tpu/optim/distri_optimizer.py")]
        got = spmd.mechanism_ledger(models)
        assert {m for _p, _ln, m in got} == {"checkpoint-step-mirror"}
        assert all(p == "bigdl_tpu/optim/optimizer.py"
                   for p, _ln, _m in got)


# ===========================================================================
# the annotation conventions bind on the REAL sources
# ===========================================================================
class TestSpmdAnnotationsOnRealTree:
    FILES = ("bigdl_tpu/optim/optimizer.py",
             "bigdl_tpu/optim/distri_optimizer.py",
             "bigdl_tpu/optim/trigger.py",
             "bigdl_tpu/parallel/grad_sync.py",
             "bigdl_tpu/checkpoint/manager.py",
             "bigdl_tpu/resilience/membership.py")

    def _models(self):
        import ast as _ast
        from tools.graftlint import spmd
        out = {}
        for rel in self.FILES:
            src = open(os.path.join(REPO, rel)).read()
            out[rel] = spmd.SpmdModel(_ast.parse(src), src, rel)
        return out

    def test_replicated_by_census(self):
        # the seeded convention: >= 25 bound `# replicated-by:` lines
        # across the training/checkpoint/membership planes
        models = self._models()
        total = sum(len(m.replicated_lines) for m in models.values())
        assert total >= 25, f"only {total} replicated-by bindings bound"

    def test_replay_boundaries_bound_to_the_expected_defs(self):
        models = self._models()
        per_file = {rel: len(m.boundary_defs)
                    for rel, m in models.items()}
        assert per_file["bigdl_tpu/optim/optimizer.py"] >= 2
        assert per_file["bigdl_tpu/optim/distri_optimizer.py"] >= 3
        assert per_file["bigdl_tpu/checkpoint/manager.py"] >= 1

    def test_docstring_mentions_never_bind(self):
        # annotations live in COMMENT tokens only: a docstring QUOTING
        # the convention (rules/spmd.py does) must not create bindings
        import ast as _ast
        from tools.graftlint import spmd
        src = ('"""Doc quoting `# replicated-by: x-mirror` '
               'in prose."""\n'
               "x = 1\n")
        m = spmd.SpmdModel(_ast.parse(src), src, "bigdl_tpu/nn/d.py")
        assert m.replicated_lines == {}
        assert spmd.mechanism_ledger([m]) == []


# ===========================================================================
# ISSUE-17 acceptance: the two historical bugs, reverted on REAL source
# ===========================================================================
class TestRevertedSpmdHazards:
    def test_last_saved_step_mirror_revert_is_caught(self):
        # the PR-7 bug: without the every-process mirror write, the
        # `last_saved_step` dedup predicate is process-0-only and the
        # checkpoint collectives under it go one-sided.  Reverting the
        # annotation (as deleting the mirror would force) fires GL401.
        src = open(os.path.join(REPO, "bigdl_tpu", "optim",
                                "optimizer.py")).read()
        needle = "# replicated-by: checkpoint-step-mirror"
        assert src.count(needle) == 2, \
            "last_saved_step dedup annotations moved — update this " \
            "surgery"
        vs = lint_source(src.replace(needle, "#"),
                         path="bigdl_tpu/optim/optimizer.py")
        hits = [v for v in vs if v.rule == "GL401"]
        assert len(hits) >= 2
        assert all("one-sided" in v.message for v in hits)

    def test_fast_forward_divisibility_revert_is_caught(self):
        # the PR-16 bug: floored per-host skip without the divisibility
        # assert mis-positions hosts after an elastic resume.  Removing
        # the guard must fire GL404 at the fast_forward_records feed.
        src = open(os.path.join(REPO, "bigdl_tpu", "optim",
                                "optimizer.py")).read()
        guard = (
            "        if rec % scale:\n"
            "            raise ValueError(\n"
            '                f"mid-epoch resume: the snapshot\'s global '
            'records "\n'
            '                f"counter ({rec}) does not divide by this '
            'run\'s records "\n'
            '                f"scale ({scale}) — the world size/process '
            'count "\n'
            '                f"changed since the snapshot was written '
            'and the "\n'
            '                f"per-host skip would mis-position the '
            'dataset; resume "\n'
            '                f"at a compatible scale or from an epoch '
            'boundary")\n')
        assert guard in src, \
            "_fast_forward guard moved — update this surgery"
        vs = lint_source(src.replace(guard, ""),
                         path="bigdl_tpu/optim/optimizer.py")
        hits = [v for v in vs if v.rule == "GL404"]
        assert len(hits) == 1
        assert "fast_forward_records" in hits[0].message

    def test_schema_bucket_content_revert_is_caught(self):
        # dropping the world-size-invariant fingerprint from the
        # checkpoint schema (the PR-16 elastic-resume contract) fires
        # GL402 on the real build_schema call
        src = open(os.path.join(REPO, "bigdl_tpu", "optim",
                                "distri_optimizer.py")).read()
        kwarg = (",\n            bucket_content="
                 "grad_sync.bucket_content_sizes(self._gs_plan))")
        assert kwarg in src, \
            "_checkpoint_schema call moved — update this surgery"
        vs = lint_source(src.replace(kwarg, ")"),
                         path="bigdl_tpu/optim/distri_optimizer.py")
        hits = [v for v in vs if v.rule == "GL402"]
        assert len(hits) == 1
        assert "bucket_content" in hits[0].message

    def test_shipped_sources_lint_clean(self):
        # the gate cuts both ways: with every fix and annotation in
        # place the real files carry zero GL4xx findings
        for rel in ("bigdl_tpu/optim/optimizer.py",
                    "bigdl_tpu/optim/distri_optimizer.py"):
            src = open(os.path.join(REPO, *rel.split("/"))).read()
            vs = [v for v in lint_source(src, path=rel)
                  if v.rule.startswith("GL4")]
            assert vs == [], "\n".join(v.render() for v in vs)


# ===========================================================================
# rule catalog invariants
# ===========================================================================
class TestCatalog:
    def test_every_rule_registered_with_metadata(self):
        rules = all_rules()
        assert len(rules) >= 13
        ids = [r.id for r in rules]
        assert ids == sorted(ids)
        for r in rules:
            assert r.id.startswith("GL") and r.name and r.description
            assert r.severity in ("error", "warning")

    def test_this_file_covers_every_rule_positively(self):
        # the acceptance criterion, enforced mechanically: each rule id
        # appears in at least one positive assertion above
        src = open(os.path.abspath(__file__)).read()
        for r in all_rules():
            assert f'"{r.id}"' in src, f"no test mentions {r.id}"


# ===========================================================================
# suppressions
# ===========================================================================
SEEDED = """\
import numpy as np

def init(shape):
    return np.random.normal(0, 1, shape)
"""


class TestSuppressions:
    def test_trailing_suppresses_that_line_only(self):
        src = ("import numpy as np\n"
               "A = np.zeros(3, dtype=np.float64)"
               "  # graftlint: disable=GL104\n"
               "B = np.zeros(3, dtype=np.float64)\n")
        vs = lint_source(src, path=LIB)
        assert [(v.rule, v.line) for v in vs] == [("GL104", 3)]

    def test_standalone_comment_suppresses_next_statement_only(self):
        src = ("import numpy as np\n"
               "# host-side precompute  graftlint: disable=GL104\n"
               "A = np.zeros(3, dtype=np.float64)\n"
               "B = np.zeros(3, dtype=np.float64)\n")
        vs = lint_source(src, path=LIB)
        assert [(v.rule, v.line) for v in vs] == [("GL104", 4)]

    def test_standalone_comment_skips_continuation_comments(self):
        # a justification block may continue below the directive; the
        # suppression lands on the next STATEMENT, not the next line
        src = ("import numpy as np\n"
               "# graftlint: disable=GL104\n"
               "# (simplex precompute, cast to f32 at the use site)\n"
               "\n"
               "A = np.zeros(3, dtype=np.float64)\n"
               "B = np.zeros(3, dtype=np.float64)\n")
        vs = lint_source(src, path=LIB)
        assert [(v.rule, v.line) for v in vs] == [("GL104", 6)]

    def test_file_level_disable(self):
        src = ("# graftlint: disable-file=GL105\n" + SEEDED)
        assert lint_source(src, path=LIB) == []

    def test_wrong_rule_id_does_not_suppress(self):
        src = ("# graftlint: disable-file=GL104\n" + SEEDED)
        assert [v.rule for v in lint_source(src, path=LIB)] == ["GL105"]

    def test_rule_name_accepted_as_alias(self):
        src = ("# graftlint: disable-file=nondeterministic-rng\n" + SEEDED)
        assert lint_source(src, path=LIB) == []

    def test_respect_suppressions_false_surfaces_everything(self):
        src = ("# graftlint: disable-file=GL105\n" + SEEDED)
        vs = lint_source(src, path=LIB, respect_suppressions=False)
        assert [v.rule for v in vs] == ["GL105"]


# ===========================================================================
# drivers: JSON schema, CLI exit codes, --changed-only
# ===========================================================================
def run_cli(*args, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        capture_output=True, text=True, cwd=cwd, env=env)


class TestCLI:
    def test_seeded_violation_nonzero_exit_with_rule_and_location(
            self, tmp_path):
        bad = tmp_path / "bigdl_tpu" / "nn"
        bad.mkdir(parents=True)
        f = bad / "seeded.py"
        f.write_text(SEEDED)
        r = run_cli(str(f))
        assert r.returncode == 1
        assert "GL105" in r.stdout
        assert "seeded.py:4" in r.stdout  # file:line

    def test_clean_file_exits_zero(self, tmp_path):
        f = tmp_path / "clean.py"
        f.write_text("x = 1\n")
        r = run_cli(str(f))
        assert r.returncode == 0

    def test_missing_path_usage_error(self):
        r = run_cli("definitely/not/a/path.py")
        assert r.returncode == 2

    def test_json_schema(self, tmp_path):
        bad = tmp_path / "bigdl_tpu"
        bad.mkdir()
        (bad / "seeded.py").write_text(SEEDED)
        r = run_cli("--json", str(bad))
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["version"] == JSON_SCHEMA_VERSION
        assert doc["tool"] == "graftlint"
        assert doc["files_scanned"] == 1
        assert doc["counts"] == {"error": 1, "warning": 0}
        (v,) = doc["violations"]
        assert set(v) == {"rule", "name", "severity", "path", "line",
                          "col", "message"}
        assert v["rule"] == "GL105" and v["line"] == 4
        assert v["severity"] == "error"

    def test_select_restricts_rules(self, tmp_path):
        f = tmp_path / "bigdl_tpu_mod.py"
        f.write_text("import numpy as np\n"
                     "A = np.zeros(3, dtype=np.float64)\n"
                     "B = np.random.rand(3)\n")
        r = run_cli("--json", "--select", "GL104", str(f))
        doc = json.loads(r.stdout)
        assert {v["rule"] for v in doc["violations"]} == {"GL104"}

    def test_list_rules_covers_catalog(self):
        r = run_cli("--list-rules")
        assert r.returncode == 0
        for rule in all_rules():
            assert rule.id in r.stdout

    def test_syntax_error_reported_not_crash(self, tmp_path):
        f = tmp_path / "bigdl_tpu_broken.py"
        f.write_text("def broken(:\n")
        r = run_cli(str(f))
        assert r.returncode == 1
        assert "GL000" in r.stdout


class TestSarifOutput:
    def test_sarif_schema_and_location(self, tmp_path):
        bad = tmp_path / "bigdl_tpu"
        bad.mkdir()
        (bad / "seeded.py").write_text(SEEDED)
        r = run_cli("--format", "sarif", str(bad))
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        (run,) = doc["runs"]
        driver = run["tool"]["driver"]
        assert driver["name"] == "graftlint"
        rule_ids_in_driver = [ru["id"] for ru in driver["rules"]]
        for rule in all_rules():
            assert rule.id in rule_ids_in_driver
        (res,) = run["results"]
        assert res["ruleId"] == "GL105"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("seeded.py")
        assert loc["region"]["startLine"] == 4
        assert loc["region"]["startColumn"] >= 1
        # results reference the driver rules by index
        assert rule_ids_in_driver[res["ruleIndex"]] == "GL105"

    def test_sarif_clean_run_has_empty_results(self, tmp_path):
        f = tmp_path / "clean.py"
        f.write_text("x = 1\n")
        r = run_cli("--format", "sarif", str(f))
        assert r.returncode == 0
        doc = json.loads(r.stdout)
        assert doc["runs"][0]["results"] == []

    def test_sarif_covers_gl3xx_with_rule_metadata(self, tmp_path):
        # ISSUE-15 satellite: CI annotations must stay complete — the
        # new family ships in tool.driver.rules and results link back
        # by ruleIndex
        wire = tmp_path / "frontend"
        wire.mkdir()
        f = wire / "srv.py"
        f.write_text(
            "class H:\n"
            "    def parse(self, body):\n"
            "        try:\n"
            "            return self.decode(body)\n"
            "        except Exception as e:\n"
            "            raise _HTTPError(400, str(e))\n")
        r = run_cli("--format", "sarif", str(f))
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        driver = doc["runs"][0]["tool"]["driver"]
        ids = [rule["id"] for rule in driver["rules"]]
        for rid in ("GL301", "GL302", "GL303"):
            assert rid in ids
            meta = driver["rules"][ids.index(rid)]
            assert meta["shortDescription"]["text"]
            assert meta["defaultConfiguration"]["level"] == "error"
        (res,) = doc["runs"][0]["results"]
        assert res["ruleId"] == "GL302"
        assert driver["rules"][res["ruleIndex"]]["id"] == "GL302"

    def test_json_flag_still_emits_graftlint_schema(self, tmp_path):
        # --json stays the graftlint schema (alias of --format json);
        # mixing it with a different --format is a usage error
        f = tmp_path / "clean.py"
        f.write_text("x = 1\n")
        r = run_cli("--json", "--format", "sarif", str(f))
        assert r.returncode == 2


FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")


class TestSarifFixture:
    """ISSUE-17 satellite: the SARIF emitter is pinned by a checked-in
    fixture (known source, known findings, known lines) and validated
    against a vendored subset of the SARIF 2.1.0 schema — CI's PR
    annotations must not drift silently."""

    def _emit(self, tmp_path):
        lib = tmp_path / "bigdl_tpu" / "parallel"
        lib.mkdir(parents=True)
        src = open(os.path.join(FIXTURES, "sarif_fixture.py")).read()
        (lib / "sarif_fixture.py").write_text(src)
        r = run_cli("--format", "sarif", str(lib / "sarif_fixture.py"))
        assert r.returncode == 1
        return json.loads(r.stdout)

    def test_fixture_output_matches_expected_results(self, tmp_path):
        doc = self._emit(tmp_path)
        got = [{
            "ruleId": res["ruleId"],
            "level": res["level"],
            "uri": os.path.basename(
                res["locations"][0]["physicalLocation"]
                ["artifactLocation"]["uri"]),
            "startLine": res["locations"][0]["physicalLocation"]
                            ["region"]["startLine"],
            "startColumn": res["locations"][0]["physicalLocation"]
                              ["region"]["startColumn"],
        } for res in doc["runs"][0]["results"]]
        expected = json.load(open(os.path.join(
            FIXTURES, "sarif_fixture.expected.json")))["results"]
        assert got == expected, (
            "SARIF output drifted from the checked-in fixture — if the "
            "change is intentional, regenerate "
            "tests/fixtures/graftlint/sarif_fixture.expected.json")

    def test_fixture_output_validates_against_sarif_schema(self,
                                                           tmp_path):
        jsonschema = pytest.importorskip("jsonschema")
        doc = self._emit(tmp_path)
        schema = json.load(open(os.path.join(
            FIXTURES, "sarif-2.1.0-subset.schema.json")))
        jsonschema.validate(doc, schema)
        # ruleIndex must point at the matching driver rule
        rules = doc["runs"][0]["tool"]["driver"]["rules"]
        for res in doc["runs"][0]["results"]:
            assert rules[res["ruleIndex"]]["id"] == res["ruleId"]

    def test_lint_ci_wrapper_emits_sarif_and_stats(self, tmp_path):
        # tools/lint_ci.sh: one call → SARIF artifact + debt dashboard,
        # exit status = the lint gate's
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        out = tmp_path / "report.sarif"
        env = dict(os.environ, PYTHONPATH=REPO,
                   GRAFTLINT_SARIF_OUT=str(out), PYTHON=sys.executable)
        r = subprocess.run(
            ["sh", os.path.join(REPO, "tools", "lint_ci.sh"),
             str(clean)],
            capture_output=True, text=True, env=env)
        assert r.returncode == 0, r.stderr
        doc = json.loads(out.read_text())
        assert doc["version"] == "2.1.0"
        assert doc["runs"][0]["results"] == []
        assert "suppressed" in r.stdout  # the --stats table header
        assert "SARIF report written" in r.stderr

    def test_lint_ci_wrapper_propagates_findings_exit(self, tmp_path):
        bad = tmp_path / "bigdl_tpu"
        bad.mkdir()
        (bad / "seeded.py").write_text(SEEDED)
        out = tmp_path / "report.sarif"
        env = dict(os.environ, PYTHONPATH=REPO,
                   GRAFTLINT_SARIF_OUT=str(out), PYTHON=sys.executable)
        r = subprocess.run(
            ["sh", os.path.join(REPO, "tools", "lint_ci.sh"),
             str(bad)],
            capture_output=True, text=True, env=env)
        assert r.returncode == 1
        doc = json.loads(out.read_text())
        assert doc["runs"][0]["results"]


class TestStatsCLI:
    SRC = ("import numpy as np\n"
           "A = np.zeros(3, dtype=np.float64)"
           "  # precomputed simplex; graftlint: disable=GL104\n"
           "B = np.zeros(3, dtype=np.float64)\n"
           "C = np.random.rand(3)\n")

    def test_stats_counts_findings_and_suppressions(self, tmp_path):
        d = tmp_path / "bigdl_tpu"
        d.mkdir()
        (d / "mod.py").write_text(self.SRC)
        r = run_cli("--stats", str(d))
        assert r.returncode == 0  # stats is a dashboard, not a gate
        lines = {ln.split()[0]: ln for ln in r.stdout.splitlines()
                 if ln.startswith("GL")}
        # GL104: one live finding, one suppressed; GL105: one finding
        assert lines["GL104"].split()[-2:] == ["1", "1"]
        assert lines["GL105"].split()[-2:] == ["1", "0"]
        # every registered rule has a row (zero-debt rows included)
        for rule in all_rules():
            assert rule.id in lines

    def test_stats_json(self, tmp_path):
        d = tmp_path / "bigdl_tpu"
        d.mkdir()
        (d / "mod.py").write_text(self.SRC)
        r = run_cli("--stats", "--json", str(d))
        doc = json.loads(r.stdout)
        assert doc["files_scanned"] == 1
        assert doc["rules"]["GL104"] == {
            "name": "float64-promotion", "findings": 1, "suppressed": 1}

    def test_stats_rejects_unsupported_flag_combos(self, tmp_path):
        # review regression: --stats must refuse flags it cannot
        # honor instead of silently reporting whole-tree numbers
        f = tmp_path / "clean.py"
        f.write_text("x = 1\n")
        assert run_cli("--stats", "--changed-only",
                       str(f)).returncode == 2
        assert run_cli("--stats", "--format", "sarif",
                       str(f)).returncode == 2

    def test_stats_debt_table_deterministically_ordered(self, tmp_path):
        # ISSUE-17 satellite: the per-file debt table is sorted by
        # (rule, path) so two runs over the same tree diff clean
        d = tmp_path / "bigdl_tpu"
        d.mkdir()
        f64 = ("import numpy as np\n"
               "A = np.zeros(3, dtype=np.float64)"
               "  # reviewed; graftlint: disable=GL104\n")
        rng = ("import numpy as np\n"
               "B = np.random.rand(3)"
               "  # reviewed; graftlint: disable=GL105\n")
        (d / "zeta.py").write_text(f64)
        (d / "alpha.py").write_text(f64 + rng)
        r1 = run_cli("--stats", str(d))
        r2 = run_cli("--stats", str(d))
        assert r1.returncode == 0
        assert r1.stdout == r2.stdout  # byte-identical across runs
        lines = r1.stdout.splitlines()
        start = next(i for i, ln in enumerate(lines)
                     if ln.startswith("suppression debt by file"))
        rows = [ln.split() for ln in lines[start + 1:]
                if ln.startswith("  GL")]
        keys = [(rule, path) for rule, path, _n in rows]
        assert keys == sorted(keys)
        # both files and both rules are present, rule-major
        assert [k[0] for k in keys] == ["GL104", "GL104", "GL105"]
        assert keys[0][1].endswith("alpha.py")
        assert keys[1][1].endswith("zeta.py")

    def test_stats_debt_table_json_is_sorted_too(self, tmp_path):
        d = tmp_path / "bigdl_tpu"
        d.mkdir()
        (d / "b.py").write_text(
            "import numpy as np\n"
            "A = np.zeros(3, dtype=np.float64)"
            "  # ok; graftlint: disable=GL104\n")
        (d / "a.py").write_text(
            "import numpy as np\n"
            "A = np.zeros(3, dtype=np.float64)"
            "  # ok; graftlint: disable=GL104\n")
        r = run_cli("--stats", "--json", str(d))
        doc = json.loads(r.stdout)
        paths = list(doc["suppressions_by_file"])
        assert paths == sorted(paths)

    def test_select_prefix_runs_a_family(self, tmp_path):
        f = tmp_path / "bigdl_tpu_mod.py"
        f.write_text("import threading\n"
                     "def fire(fn):\n"
                     "    threading.Thread(target=fn).start()\n"
                     "x = __import__('numpy').random.rand(3)\n")
        r = run_cli("--json", "--select", "GL2", str(f))
        doc = json.loads(r.stdout)
        assert {v["rule"] for v in doc["violations"]} == {"GL204"}

    def test_default_paths_cover_tools(self, tmp_path):
        # ISSUE-15 satellite: the bare CLI gate extends past bigdl_tpu
        # to tools/ (threaded helper code is product too) and to
        # nothing else at the root.  Exercised against a stub tree so
        # the default-path resolution is gated end-to-end without a
        # full-repo scan
        # (the real tree's cleanliness is TestRealTree's job).
        (tmp_path / "bigdl_tpu").mkdir()
        (tmp_path / "bigdl_tpu" / "m.py").write_text("x = 1\n")
        (tmp_path / "tools").mkdir()
        (tmp_path / "tools" / "t.py").write_text("y = 2\n")
        (tmp_path / "script.py").write_text("z = 3\n")
        r = run_cli("--json", cwd=str(tmp_path))
        doc = json.loads(r.stdout)
        assert doc["files_scanned"] == 2


# ===========================================================================
# suppression-debt baseline (ISSUE-15 satellite)
# ===========================================================================
@pytest.fixture(scope="module")
def full_tree_scan():
    """ONE whole-tree scan (gate result + suppression stats) shared by
    every full-tree gate in this module — the scan costs ~35s on the
    CPU host and three tests used to repeat it."""
    from tools.graftlint import core
    old = os.getcwd()
    os.chdir(REPO)  # baseline keys and violation paths are repo-relative
    try:
        return core.lint_paths_with_stats(["bigdl_tpu", "tools"])
    finally:
        os.chdir(old)


class TestSuppressionBaseline:
    """Suppression debt can shrink silently, never grow silently: the
    checked-in ``tools/graftlint/suppressions_baseline.json`` freezes
    per-file per-rule counts; growing one requires regenerating the
    baseline (``--stats --write-baseline`` — a reviewed diff) AND a
    triage-table row in tools/graftlint/README.md."""

    def test_checked_in_baseline_loads(self):
        from tools.graftlint import core
        doc = core.load_baseline()
        assert doc["schema_version"] == core.BASELINE_SCHEMA_VERSION
        assert doc["suppressions"], "empty baseline — regenerate"

    def test_no_net_new_suppression_debt(self, full_tree_scan):
        from tools.graftlint import core
        _, stats = full_tree_scan
        delta = core.suppression_debt_delta(stats, core.load_baseline())
        assert delta == [], (
            "net-new `# graftlint: disable=` entries:\n  "
            + "\n  ".join(delta)
            + "\nEither remove the suppression, or (reviewed) "
              "regenerate the baseline with `python -m tools.graftlint "
              "--stats --write-baseline` AND add a triage-table row "
              "to tools/graftlint/README.md")

    def test_every_baseline_file_has_a_readme_triage_mention(self):
        from tools.graftlint import core
        doc = core.load_baseline()
        readme = open(os.path.join(REPO, "tools", "graftlint",
                                   "README.md")).read()
        for path, rules in sorted(doc["suppressions"].items()):
            if not any(rules.values()):
                continue
            assert os.path.basename(path) in readme, (
                f"{path} carries suppressions but has no triage row "
                "in tools/graftlint/README.md")

    def test_delta_detects_growth_and_tolerates_shrink(self):
        from tools.graftlint.core import suppression_debt_delta
        baseline = {"suppressions": {"a.py": {"GL201": 2},
                                     "b.py": {"GL104": 1}}}
        grown = {"suppressions_by_file": {"a.py": {"GL201": 3}}}
        assert suppression_debt_delta(grown, baseline) == [
            "a.py: GL201 suppressions 3 > baseline 2"]
        shrunk = {"suppressions_by_file": {"a.py": {"GL201": 1}}}
        assert suppression_debt_delta(shrunk, baseline) == []
        new_file = {"suppressions_by_file": {"c.py": {"GL302": 1}}}
        assert suppression_debt_delta(new_file, baseline) == [
            "c.py: GL302 suppressions 1 > baseline 0"]

    def test_write_baseline_cli_round_trip(self, tmp_path):
        d = tmp_path / "bigdl_tpu"
        d.mkdir()
        (d / "mod.py").write_text(
            "import numpy as np\n"
            "A = np.zeros(3, dtype=np.float64)"
            "  # reviewed; graftlint: disable=GL104\n")
        out = tmp_path / "baseline.json"
        r = run_cli("--stats", "--write-baseline", str(out), str(d),
                    cwd=str(tmp_path))
        assert r.returncode == 0
        doc = json.loads(out.read_text())
        assert doc["schema_version"] == 1
        assert doc["suppressions"] == {"bigdl_tpu/mod.py": {"GL104": 1}}

    def test_write_baseline_requires_stats(self, tmp_path):
        f = tmp_path / "clean.py"
        f.write_text("x = 1\n")
        r = run_cli("--write-baseline", str(tmp_path / "b.json"),
                    str(f))
        assert r.returncode == 2
        assert "--stats" in r.stderr


class TestChangedOnlyImportClosure:
    def test_importers_of_changed_modules_are_relinted(self, tmp_path):
        from tools.graftlint import core
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        a = pkg / "locks.py"
        a.write_text("import threading\nLOCK = threading.Lock()\n")
        b = pkg / "user_abs.py"
        b.write_text("from pkg.locks import LOCK\n")
        c = pkg / "user_rel.py"
        c.write_text("from . import locks\n")
        d = pkg / "bystander.py"
        d.write_text("x = 1\n")
        files = [str(a), str(b), str(c), str(d)]
        got = core.expand_changed_with_importers(
            files, [str(a)], root=str(tmp_path))
        assert got == [str(a), str(b), str(c)]

    def test_plain_import_reaches_ancestor_packages(self, tmp_path):
        # review regression: `import a.b.c` executes a/__init__ and
        # a/b/__init__ too, so a changed package __init__ re-lints
        # importers using the plain-import form as well
        from tools.graftlint import core
        pkg = tmp_path / "pkg"
        sub = pkg / "sub"
        sub.mkdir(parents=True)
        init = pkg / "__init__.py"
        init.write_text("")
        (sub / "__init__.py").write_text("")
        leaf = sub / "leaf.py"
        leaf.write_text("x = 1\n")
        user = tmp_path / "user.py"
        user.write_text("import pkg.sub.leaf\n")
        got = core.expand_changed_with_importers(
            [str(leaf), str(user)], [str(init)], root=str(tmp_path))
        assert got == [str(user)]

    def test_no_changes_scans_nothing(self, tmp_path):
        from tools.graftlint import core
        f = tmp_path / "m.py"
        f.write_text("x = 1\n")
        assert core.expand_changed_with_importers(
            [str(f)], [], root=str(tmp_path)) == []

    def test_module_name_of(self, tmp_path):
        from tools.graftlint import core
        root = str(tmp_path)
        assert core.module_name_of(
            str(tmp_path / "a" / "b.py"), root) == "a.b"
        assert core.module_name_of(
            str(tmp_path / "a" / "__init__.py"), root) == "a"
        assert core.module_name_of(
            str(tmp_path.parent / "outside.py"), root) is None


class TestChangedOnly:
    def test_filter_changed_intersects_normalized(self):
        files = ["bigdl_tpu/nn/module.py", "bigdl_tpu/optim/sgd.py"]
        changed = {"./bigdl_tpu/nn/module.py", "tests/test_x.py"}
        assert filter_changed(files, changed) == ["bigdl_tpu/nn/module.py"]

    def test_filter_changed_matches_absolute_targets(self):
        # lint targets may be absolute while git reports repo-relative
        # paths anchored at the toplevel — both sides resolve to abs
        files = [os.path.join(os.getcwd(), "bigdl_tpu/nn/module.py")]
        changed = {"bigdl_tpu/nn/module.py"}
        assert filter_changed(files, changed) == files

    def test_changed_only_sees_changes_with_absolute_target(self):
        # end to end against the real repo: this test file itself is
        # new/modified, so a --changed-only run over tests/ must find it
        from tools.graftlint import core
        changed = core.changed_files("HEAD")
        assert all(os.path.isabs(c) for c in changed)
        me = os.path.abspath(__file__)
        if me in changed:  # true in the PR worktree, not after merge
            got = filter_changed([me], changed)
            assert got == [me]

    def test_changed_only_with_no_matching_changes_scans_nothing(
            self, tmp_path):
        # outside any git repo state for these paths: empty scan, exit 0
        f = tmp_path / "bigdl_tpu_x.py"
        f.write_text(SEEDED)
        r = run_cli("--json", "--changed-only", "--base", "HEAD",
                    str(f), cwd=str(tmp_path))
        assert r.returncode == 0
        assert json.loads(r.stdout)["files_scanned"] == 0


# ===========================================================================
# THE GATE: the real tree is violation-free
# ===========================================================================
class TestRealTree:
    def test_bigdl_tpu_lints_clean(self, full_tree_scan):
        result, _ = full_tree_scan
        assert result.files_scanned > 50
        lib = [v for v in result.violations
               if v.path.startswith("bigdl_tpu")]
        msgs = "\n".join(v.render() for v in lib)
        assert lib == [], (
            "graftlint gate: fix the hazard or add a reviewed inline "
            "suppression with a justification:\n" + msgs)

    def test_tools_lint_clean_too(self, full_tree_scan):
        # ISSUE-15 satellite: the gate covers the tools/ tree
        # (threaded helper code is product code) — same bar as the
        # library: zero findings, not just zero errors
        result, _ = full_tree_scan
        rest = [v for v in result.violations
                if not v.path.startswith("bigdl_tpu")]
        msgs = "\n".join(v.render() for v in rest)
        assert rest == [], msgs

    def test_telemetry_package_lints_clean(self):
        """The telemetry package rides inside the bigdl_tpu gate above,
        but its inertness contract (host-side only — no jit-reachable
        syncs, no tensor branches) earns an explicit standalone gate:
        a regression here means telemetry code leaked into traced
        scope."""
        result = lint_paths([os.path.join(REPO, "bigdl_tpu",
                                          "telemetry")])
        assert result.files_scanned >= 5
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_ops_package_lints_clean(self):
        """Standalone gate for the custom-kernel modules (round-10,
        ISSUE-8): ops/ holds pallas kernel bodies plus their
        supported()/impl gating — all kernel-choice branching must be
        host-static (shape/dtype/config), never tensor-valued, and
        kernel wrappers must stay sync-free.  A violation here means a
        kernel gate leaked into traced scope (see the catalog note
        "kernel gating is host code")."""
        result = lint_paths([os.path.join(REPO, "bigdl_tpu", "ops")])
        assert result.files_scanned >= 5  # incl. pallas_int8_gemm.py
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_int8_gemm_modules_lint_clean(self):
        """Standalone gate for the int8 speed path (the quantized
        inference PR): the GEMM wrapper's mode/impl/supported() gating
        and the quantized layers' GEMM-engagement checks
        (``_gemm_engages``) are host code by the same contract as every
        kernel gate — static shape/dtype/config facts only (catalog
        note "int8 kernel gating is host code").  A violation here
        means quantization dispatch grew a tensor-valued branch or a
        traced-scope sync."""
        result = lint_paths([
            os.path.join(REPO, "bigdl_tpu", "ops",
                         "pallas_int8_gemm.py"),
            os.path.join(REPO, "bigdl_tpu", "nn", "quantized.py")])
        assert result.files_scanned == 2
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_resilience_package_lints_clean(self):
        """Standalone gate for the resilience package (ISSUE-10): the
        fault injector, health state machines and ReplicaSet router are
        pure host-side bookkeeping (threads, locks, clocks — no jax in
        the hot path), and the numeric guard's device half lives in
        optim/ riding the replay fetch (catalog note "the numeric guard
        rides the replay boundary").  A violation here means resilience
        code grew a traced-scope sync or tensor branch — exactly the
        hazard a recovery path must never add to the driver."""
        result = lint_paths([os.path.join(REPO, "bigdl_tpu",
                                          "resilience")])
        assert result.files_scanned >= 5
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_frontend_package_lints_clean(self):
        """Standalone gate for the wire frontend (ISSUE-14): the HTTP
        server, QoS admission, hot cutover and autoscaler are pure
        host-side plumbing (stdlib http.server threads, token buckets,
        condition-waited drain counters — no jax import anywhere in
        the package), and the new threaded modules carry
        `# guarded-by:` annotations from day one.  GL1xx and GL2xx
        both run here; a violation means the wire plane grew either a
        traced-scope hazard or an unguarded-shared-state regression.
        ISSUE-19 adds the event-loop core (eventloop.py, http1.py):
        loop-owned state rides the documented single-owner discipline,
        cross-thread handoffs stay lock-guarded."""
        result = lint_paths([os.path.join(REPO, "bigdl_tpu",
                                          "frontend")])
        assert result.files_scanned == 7
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_frontend_package_clean_under_gl2_select(self):
        """The concurrency family alone over the frontend package —
        the `--select GL2` gate ISSUE-14 names for the new threaded
        modules (wire inflight counters, scale locks, controller
        state)."""
        result = lint_paths([os.path.join(REPO, "bigdl_tpu",
                                          "frontend")],
                            select=["GL2"])
        assert result.files_scanned == 7
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_decode_serving_modules_lint_clean(self):
        """Standalone gate for the sharded-serving + continuous-
        batching modules (ISSUE-20): serving/sharded.py is pure
        host-side placement plumbing (device grouping, per-slot mesh
        construction — its one jax surface is the off-path
        ``device_put`` warmup in ``_build_replica``), and
        serving/decode.py holds the GL106 discipline at decode
        granularity — every prefill bucket, the cache splice and the
        step executable AOT-compile in the constructor, so a
        steady-state retrace or a traced-scope sync here means the
        iteration scheduler regressed into trace-per-request."""
        result = lint_paths([
            os.path.join(REPO, "bigdl_tpu", "serving", "sharded.py"),
            os.path.join(REPO, "bigdl_tpu", "serving", "decode.py")])
        assert result.files_scanned == 2
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_decode_serving_modules_clean_under_gl2_select(self):
        """The concurrency family alone over the two ISSUE-20 modules
        — the decode scheduler's cross-thread surface (queue,
        lifecycle flags, active count) carries `# guarded-by: _cond`
        contracts from day one; the slot bookkeeping and device caches
        are single-owner (the scheduler thread) by the module's
        documented thread model."""
        result = lint_paths([
            os.path.join(REPO, "bigdl_tpu", "serving", "sharded.py"),
            os.path.join(REPO, "bigdl_tpu", "serving", "decode.py")],
            select=["GL2"])
        assert result.files_scanned == 2
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_obs_plane_modules_lint_clean(self):
        """Standalone gate for the observability round-2 surface
        (ISSUE-11): the admin plane, flight recorder and request
        context are pure host-side plumbing (http.server thread,
        JSONL stream, id minting — no jax anywhere near a hot path),
        and the two reporting tools are offline file-joiners.  A
        violation here means observability code grew a traced-scope
        hazard — exactly what the "events ride existing boundaries"
        catalog note forbids."""
        result = lint_paths([
            os.path.join(REPO, "bigdl_tpu", "telemetry", "admin.py"),
            os.path.join(REPO, "bigdl_tpu", "telemetry", "flight.py"),
            os.path.join(REPO, "bigdl_tpu", "telemetry", "context.py"),
            os.path.join(REPO, "tools", "obs_report.py"),
            os.path.join(REPO, "tools", "trace_report.py"),
        ])
        assert result.files_scanned == 5
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_threaded_packages_clean_under_gl2_select(self):
        """Standalone concurrency gate (ISSUE-13): the threaded
        serving/resilience/telemetry/checkpoint plane must hold its
        documented locking contracts under the GL2xx family alone —
        `# guarded-by:` annotations honored, no non-reentrant
        re-takes, settle-every-path, thread lifecycle, wait
        predicates, no blocking under locks.  A violation here is a
        regression of exactly the bug classes the PR 5/10/11 review
        rounds kept finding by repro."""
        result = lint_paths(
            [os.path.join(REPO, "bigdl_tpu", p)
             for p in ("serving", "resilience", "telemetry",
                       "checkpoint", "frontend")],
            select=["GL2"])
        assert result.files_scanned >= 23
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs

    def test_guarded_by_annotations_are_bound(self):
        """The annotation rollout is real, not cosmetic: the thread
        model must bind `# guarded-by:` declarations in the core
        threaded classes (a silently-unparsed annotation would turn
        GL201 into a no-op)."""
        import ast as _ast

        from tools.graftlint import threads as _threads
        expect = {
            ("bigdl_tpu/serving/batcher.py", "RequestBatcher", "_q"),
            ("bigdl_tpu/serving/registry.py", "ModelRegistry",
             "_services"),
            ("bigdl_tpu/resilience/replica_set.py", "ReplicaSet",
             "_inflight"),
            ("bigdl_tpu/resilience/health.py", "ReplicaHealth",
             "_probe_inflight"),
            ("bigdl_tpu/resilience/membership.py", "ClusterMembership",
             "_epochs"),
            ("bigdl_tpu/telemetry/registry.py", "MetricRegistry",
             "_metrics"),
            ("bigdl_tpu/telemetry/tracer.py", "Tracer", "_events"),
        }
        for rel, cls, attr in sorted(expect):
            src = open(os.path.join(REPO, rel)).read()
            model = _threads.ThreadModel(_ast.parse(src), src, rel)
            guards = model.guards_for(cls)
            assert attr in guards, f"{rel}: {cls}.{attr} unbound"

    def test_resource_annotations_are_bound(self):
        """The GL3xx rollout is real, not cosmetic: the resource model
        must bind the `# acquires:`/`# releases:` declarations in the
        core threaded modules (a silently-unparsed annotation would
        turn GL301/GL303 into no-ops — same gate as guarded-by)."""
        import ast as _ast

        from tools.graftlint import resources as _resources
        expect = {
            # path -> (resource, must-be-in-def-acquires-names)
            "bigdl_tpu/frontend/server.py": (
                "wire_inflight", {"enter", "_resolve_pinned"},
                {"exit"}),
            "bigdl_tpu/serving/batcher.py": ("queue_rows", set(),
                                             set()),
            "bigdl_tpu/resilience/health.py": ("probe_slot", set(),
                                               set()),
            "bigdl_tpu/resilience/replica_set.py": ("rs_inflight",
                                                    set(), set()),
            "bigdl_tpu/serving/registry.py": ("deploy_reservation",
                                              set(), set()),
            # ISSUE-16 satellite: the latest_valid() GC pin must hold
            # until restore_into finishes applying the snapshot
            "bigdl_tpu/checkpoint/manager.py": (
                "snapshot_pin", {"latest_valid", "restore"},
                {"unpin"}),
        }
        for rel, (res, acq_defs, rel_defs) in sorted(expect.items()):
            src = open(os.path.join(REPO, rel)).read()
            model = _resources.ResourceModel(_ast.parse(src), src, rel)
            acquired = {r for _l, toks in model.acquire_stmt_sites()
                        for r in toks}
            for toks in model.name_acquires.values():
                acquired |= toks
            released = {r for _l, toks in model.release_stmt_sites()
                        for r in toks}
            for toks in model.name_releases.values():
                released |= toks
            assert res in acquired, f"{rel}: {res} acquire unbound"
            assert res in released, f"{rel}: {res} release unbound"
            for name in acq_defs:
                assert res in model.name_acquires.get(name, set()), \
                    f"{rel}: def {name} missing `# acquires: {res}`"
            for name in rel_defs:
                assert res in model.name_releases.get(name, set()), \
                    f"{rel}: def {name} missing `# releases: {res}`"

    def test_checkpoint_package_lints_clean(self):
        """Same standalone discipline for the checkpoint package: its
        one device fetch (snapshot.capture_to_host) is only legal at
        the driver's replay boundary (catalog note "snapshot fetches
        ride the replay boundary") and everything else is host-side
        file I/O — a violation here means checkpoint code grew a
        traced-scope sync or a fetch outside that boundary."""
        result = lint_paths([os.path.join(REPO, "bigdl_tpu",
                                          "checkpoint")])
        assert result.files_scanned >= 5
        msgs = "\n".join(v.render() for v in result.violations)
        assert result.violations == [], msgs


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
