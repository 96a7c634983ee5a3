"""Per-layer timing + jax-profiler integration.

Reference: ``AbstractModule.scala:254-287`` — every module self-times
``forwardTime``/``backwardTime``; ``getTimes()`` aggregates per layer and
conv layers break out im2col time.

TPU redesign: under jit the layers FUSE — per-layer wall-time inside the
compiled step doesn't exist as an observable (that's the point of XLA).
So profiling splits into the two things that are actually measurable:

- :func:`get_times` — eager per-layer forward/backward timing of a module
  tree on real inputs (the ``getTimes()`` analog, for finding the slow
  layer before jit);
- :func:`profile_step` — wraps a jit'd step with ``jax.profiler`` traces
  (view in TensorBoard / xprof, where XLA attributes time per fused op);
  ``named_scope`` annotations give HLO ops layer-derived names.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Tuple

import jax

from bigdl_tpu.nn.module import Container, Module


class LayerTime:
    __slots__ = ("name", "forward_s", "backward_s")

    def __init__(self, name: str, forward_s: float, backward_s: float):
        self.name = name
        self.forward_s = forward_s
        self.backward_s = backward_s

    def __repr__(self):
        return (f"{self.name}: fwd {self.forward_s * 1e3:.3f}ms "
                f"bwd {self.backward_s * 1e3:.3f}ms")


def _block(x):
    return jax.block_until_ready(x)


def get_times(model: Module, input, *, repeats: int = 3,
              rng: Optional[jax.Array] = None) -> List[LayerTime]:
    """Per-layer eager forward+backward timings (reference
    ``AbstractModule.getTimes``).  Walks a Container tree, timing each
    leaf's apply and its vjp on the activations produced by the previous
    layers.  Returns leaves in execution order plus a TOTAL row."""
    model._ensure_init()
    times: List[LayerTime] = []

    def leaf_time(m: Module, params, state, x) -> Tuple[Any, float, float]:
        # forward
        fwd = lambda p, xx: m.apply(p, state, xx, training=False, rng=rng)[0]
        _block(fwd(params, x))  # compile/warm
        t0 = time.perf_counter()
        for _ in range(repeats):
            y = _block(fwd(params, x))
        f_s = (time.perf_counter() - t0) / repeats
        # backward (vjp wrt params+input, like updateGradInput+accGrad)
        y0, vjp = jax.vjp(fwd, params, x)
        ct = jax.tree_util.tree_map(lambda a: a, y0)
        _block(vjp(ct))
        t0 = time.perf_counter()
        for _ in range(repeats):
            _block(vjp(ct))
        b_s = (time.perf_counter() - t0) / repeats
        return y0, f_s, b_s

    def walk(m: Module, params, state, x, prefix=""):
        label = f"{prefix}{m.name}"
        if isinstance(m, Container) and m.modules:
            from bigdl_tpu.nn.module import Sequential
            if isinstance(m, Sequential):
                out = x
                for i, c in enumerate(m.modules):
                    out = walk(c, params[str(i)], state[str(i)], out,
                               prefix=label + "/")
                return out
            # non-sequential containers: time as one unit
        y, f_s, b_s = leaf_time(m, params, state, x)
        times.append(LayerTime(label, f_s, b_s))
        return y

    t0 = time.perf_counter()
    walk(model, model._params, model._state, input)
    total = time.perf_counter() - t0
    times.append(LayerTime("TOTAL(walk)", total, 0.0))
    return times


def format_times(times: List[LayerTime]) -> str:
    """Pretty table, slowest forward first (reference ``getTimes`` print
    style)."""
    body = sorted((t for t in times if not t.name.startswith("TOTAL")),
                  key=lambda t: -(t.forward_s + t.backward_s))
    width = max((len(t.name) for t in times), default=10)
    lines = [f"{'layer':<{width}}  {'fwd(ms)':>9}  {'bwd(ms)':>9}"]
    for t in body:
        lines.append(f"{t.name:<{width}}  {t.forward_s * 1e3:>9.3f}  "
                     f"{t.backward_s * 1e3:>9.3f}")
    return "\n".join(lines)


def profile_window(seconds: float, log_dir: Optional[str] = None) -> str:
    """Wall-clock ``jax.profiler`` capture: whatever the process is
    doing for the next ``seconds`` lands in the xplane trace (open with
    TensorBoard) — the spans of every enabled telemetry ``Tracer``
    included, as ``bigdl:<category>:<name>`` events on the host plane.
    The admin plane's ``/profile?seconds=N`` endpoint is a thin shim
    over this — the on-demand deep dive for a live serving process,
    where there is no single ``step_fn`` to hand to
    :func:`profile_step`.  Returns the log dir.

    Same divergence note as :func:`profile_step`: this is the opt-in,
    off-the-hot-path tool — never the always-on path (the always-on
    surfaces are the tracer and /metrics, which never sync)."""
    import tempfile
    import time as _time

    if log_dir is None:
        log_dir = tempfile.mkdtemp(prefix="bigdl_tpu_profile_")
    with jax.profiler.trace(log_dir):
        _time.sleep(float(seconds))
    return log_dir


def profile_step(step_fn, *args, log_dir: str, steps: int = 3):
    """Run ``step_fn(*args)`` under the jax profiler (xplane trace in
    ``log_dir``; open with TensorBoard).  The jit'd step's per-op times
    carry the layer names annotated by jit tracing.

    The deliberate divergence from the driver's inertness rule: this
    function exists to sync (``block_until_ready`` per step) — it is
    the opt-in, off-the-hot-path deep dive, never the always-on path.
    """
    # warmup/compile outside the trace
    _block(step_fn(*args))
    with jax.profiler.trace(log_dir):
        out = None
        for _ in range(steps):
            out = _block(step_fn(*args))
    return out
