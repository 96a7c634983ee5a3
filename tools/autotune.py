"""Bench-driven autotuner — successive-halving search over the
exposed config space (ROADMAP item 3; in the spirit of TVM
arXiv:1802.04799 and Learning to Optimize Tensor Programs
arXiv:1805.08166).

Eight PRs grew a measured knob space — ``steps_per_dispatch`` K,
``grad_bucket_bytes``, ``grad_wire_dtype``, ``kernel_impl``,
activation-memory policy, serving bucket sets /
``serving_batch_timeout_ms`` — whose defaults were hand-recorded
(``bench.PRODUCTION_K``, tuning notes in bench.py docstrings).  This
driver makes them self-tuning: a declarative per-workload discrete
grid (``WORKLOADS``) is searched by successive halving, every trial
measured through the EXISTING measurement substrate —
``bench._measure``'s warmup-discarded windows for training workloads,
a closed-loop offered-load burst (the ``bench.py --serving`` harness
shape) for serving — with the PR 6 steady-state discipline applied to
the window samples (windows outside ±15% of the trimmed median are
excluded from the score, exclusions counted, never silent).  Winners
are written to a schema-versioned, checked-in ``tuned_configs.json``
(per-workload best config + measurement provenance) that the runtime
consumes as defaults through ``bigdl_tpu.utils.tuned`` (resolution:
explicit setter > ``BIGDL_TPU_*`` env > tuned entry for
``workload@backend`` > dataclass default).

Search contract (gated in tests/test_autotune.py):

- **Budget is hard**: total MEASURED windows across all rungs ≤
  ``--budget``; the rung plan (trial count + windows per trial per
  rung) is logged in the output JSON — no silent caps.  Warmup
  windows are discarded by ``bench._measure`` before samples exist
  and are not budgeted, same as every bench entry.
- **Deterministic given the same measurements**: trials enter in
  canonical-key order and every ranking sorts on
  ``(-score, config_key)`` where ``config_key`` is the trial's
  ``json.dumps(config, sort_keys=True)`` — an exact score tie goes to
  the lexicographically smallest canonical key.
- **Early rungs short, survivors confirmed**: every rung starts at one
  window per trial and leftover budget is spent from the LAST rung
  backwards (up to ``--full-windows``), so the final survivor always
  gets the longest confirmation run the budget allows.  Samples
  accumulate across rungs — a survivor's score at rung r uses all its
  windows so far.
- **Grid axes that cannot be measured here are pruned LOUDLY**: axes
  marked TPU-only (``kernel_impl`` — interpret-mode pallas on a CPU
  host is correctness emulation, not a perf signal) or
  multi-device-only (the grad-sync wire knobs) are dropped with the
  reason recorded in the output JSON; the knob then simply keeps its
  config-chain default at runtime.

CLI::

    python -m tools.autotune --workload ptb_lstm [--budget 40]
        [--out tuned_configs.json] [--full-windows 4] [--eta 2]
        [--smoke] [--dry-run]
    python -m tools.autotune --list

Prints ONE JSON line (the bench discipline) with the search result;
``--dry-run`` searches without writing the tuned file.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import logging
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python tools/autotune.py` and -m both work
    sys.path.insert(0, REPO)

logger = logging.getLogger("bigdl_tpu.autotune")

SCORE_METRIC = "units_per_sec_trimmed_median_steady"


# ---------------------------------------------------------------- grid
@dataclasses.dataclass(frozen=True)
class Axis:
    """One tunable knob: a ``Config`` field name plus its candidate
    values.  ``requires`` gates measurability ("" always, "tpu" real
    Mosaic hardware, "multidevice" a >1-chip mesh); ``why`` is the
    prune reason recorded when the gate fails."""
    knob: str
    values: tuple
    requires: str = ""
    why: str = ""


@dataclasses.dataclass(frozen=True)
class Workload:
    """A named tuning target: its grid and its trial runner factory.
    ``runner(smoke)`` returns ``measure(trial, windows, rung) ->
    [units/sec per window]``."""
    name: str
    kind: str  # "training" | "serving"
    axes: Tuple[Axis, ...]
    smoke_axes: Tuple[Axis, ...]
    runner: Callable


def prune_axes(axes: Sequence[Axis], backend: str,
               n_devices: int) -> Tuple[List[Axis], Dict[str, str]]:
    """Drop grid axes the current host cannot produce a real perf
    signal for; the returned reasons are logged in the output JSON
    (never silently)."""
    kept, pruned = [], {}
    for ax in axes:
        if ax.requires == "tpu" and backend != "tpu":
            pruned[ax.knob] = ax.why
        elif ax.requires == "multidevice" and n_devices < 2:
            pruned[ax.knob] = ax.why
        else:
            kept.append(ax)
    return kept, pruned


def build_grid(axes: Sequence[Axis]) -> List[dict]:
    """Cartesian product of the axes, in declared axis/value order
    (deterministic)."""
    if not axes:
        return [{}]
    names = [ax.knob for ax in axes]
    return [dict(zip(names, combo))
            for combo in itertools.product(*(ax.values for ax in axes))]


def config_key(cfg: dict) -> str:
    """Canonical trial identity — also the documented tie-break key."""
    return json.dumps(cfg, sort_keys=True)


# ------------------------------------------------------ scoring
def steady_filter(samples: Sequence[float]) -> Tuple[List[float], int]:
    """The PR 6 steady-state discipline — ``bench.steady_windows``,
    the SAME implementation ``bench.scaling_child`` reads, so the two
    exclusion accountings stay comparable.  ``min_samples=4`` here
    (vs the bench default 3) because early rungs accumulate one window
    at a time and 1-3 windows carry no spread to filter on.  A
    uniformly-unsteady trial scores on the reference rate with EVERY
    window counted excluded — never a silent fall-back to the raw
    set."""
    import bench
    kept, excluded, ref = bench.steady_windows(samples, min_samples=4)
    if not kept:
        return [ref], excluded
    return kept, excluded


def score_samples(samples: Sequence[float]) -> Tuple[float, int]:
    """(score, excluded_windows): trimmed-median units/sec over the
    steady windows — the same ``bench._stats`` summary every bench
    entry reports, so rankings are made on the numbers the captures
    already audit."""
    import bench
    steady, excluded = steady_filter(samples)
    _, stats = bench._stats(steady)
    return stats.get("trimmed_median", stats["median"]), excluded


# ------------------------------------------------ successive halving
def plan_rungs(n_configs: int, budget: int, eta: int = 2,
               full_windows: int = 4) -> List[Tuple[int, int]]:
    """Deterministic rung schedule under a HARD window budget.

    Survivor ladder: ``n, ceil(n/eta), …, 1``.  Every rung starts at
    one window per trial (the minimum that ranks anything); leftover
    budget is then spent from the last rung backwards, up to
    ``full_windows`` per trial — survivors earn confirmation windows
    first.  Raises when the budget cannot give every config even one
    window per rung (an unmeasured config must never be silently
    dropped)."""
    if n_configs < 1:
        raise ValueError("empty grid — nothing to tune")
    ladder = [n_configs]
    while ladder[-1] > 1:
        ladder.append(math.ceil(ladder[-1] / eta))
    windows = [1] * len(ladder)
    minimal = sum(ladder)
    if budget < minimal:
        raise ValueError(
            f"budget {budget} windows cannot rank {n_configs} configs "
            f"— the minimal successive-halving schedule (1 window per "
            f"trial per rung, survivor ladder {ladder}) needs "
            f"{minimal}; raise --budget or shrink the grid")
    spent = minimal
    for r in range(len(ladder) - 1, -1, -1):
        while windows[r] < full_windows and spent + ladder[r] <= budget:
            windows[r] += 1
            spent += ladder[r]
    return list(zip(ladder, windows))


def successive_halving(trials: Sequence[dict], measure: Callable,
                       budget: int, eta: int = 2,
                       full_windows: int = 4) -> dict:
    """Run the search; returns the result document (best config,
    per-rung log, leaderboard, window accounting).

    ``measure(trial, windows, rung)`` returns one units/sec sample per
    window.  Determinism: trials are processed in canonical-key order
    and all rankings tie-break on that key (see module docstring)."""
    plan = plan_rungs(len(trials), budget, eta, full_windows)
    state = sorted(
        ({"config": dict(t), "key": config_key(t), "samples": []}
         for t in trials), key=lambda s: s["key"])
    if len({s["key"] for s in state}) != len(state):
        raise ValueError("duplicate configs in grid")
    windows_total = 0
    rung_log = []
    alive = list(state)
    for rung, (n_r, w_r) in enumerate(plan):
        alive = alive[:n_r]
        for t in alive:
            samples = [float(s) for s in measure(t["config"], w_r, rung)]
            t["samples"].extend(samples)
            windows_total += len(samples)
        for t in alive:
            t["score"], t["excluded"] = score_samples(t["samples"])
        alive.sort(key=lambda t: (-t["score"], t["key"]))
        survivors = plan[rung + 1][0] if rung + 1 < len(plan) else 1
        rung_log.append({
            "rung": rung, "trials": n_r, "windows_per_trial": w_r,
            "windows_used": n_r * w_r,
            "survivors": min(survivors, n_r),
            "best": alive[0]["config"],
            "best_score": alive[0]["score"],
        })
        logger.info("rung %d: %d trials x %d windows -> best %s @ %.1f",
                    rung, n_r, w_r, alive[0]["key"], alive[0]["score"])
    if windows_total > budget:
        raise RuntimeError(  # a runner returned more samples than asked
            f"measured {windows_total} windows > budget {budget}")
    best = alive[0]
    return {
        "best_config": best["config"],
        "score": best["score"],
        "score_metric": SCORE_METRIC,
        "n_configs": len(trials),
        "rungs": rung_log,
        "windows_total": windows_total,
        "budget": budget,
        "excluded_windows": sum(t.get("excluded", 0) for t in state),
        "leaderboard": [{"config": t["config"],
                         "score": t["score"],
                         "windows": len(t["samples"])}
                        for t in alive],
    }


# ------------------------------------------------------ trial runners
def _ptb_runner(smoke: bool) -> Callable:
    """PTB word-LM training trials through ``bench._measure`` (the PTB
    bench entry's exact recipe, shortened)."""
    import jax.numpy as jnp
    import numpy as np

    import bench
    from bigdl_tpu import nn
    from bigdl_tpu.models.rnn import ptb_model

    if smoke:
        vocab, hidden, layers, batch, seq, iters, unroll = \
            64, 16, 1, 4, 8, 2, 1
    else:
        vocab, hidden, layers, batch, seq, iters, unroll = \
            10000, 650, 2, 20, 35, 8, 5
    rng = np.random.default_rng(0)  # same data every trial: the only
    px = jnp.asarray(rng.integers(  # variance across trials is timing
        0, vocab, (batch, seq)).astype(np.int32))
    py = jnp.asarray(rng.integers(
        0, vocab, (batch, seq)).astype(np.int32))

    def measure(trial, windows, rung):
        model = ptb_model(vocab, hidden, hidden, layers,
                          scan_unroll=unroll,
                          kernel_impl=trial.get("kernel_impl"))
        samples, _ca, _path = bench._measure(
            model, batch, windows, iters, x=px, y=py,
            criterion=nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
            units_per_step=batch * seq,
            fuse_k=trial.get("steps_per_dispatch", 1),
            warmup_windows=1,
            activation_memory=trial.get("activation_memory"))
        return samples

    return measure


def _wide_deep_runner(smoke: bool) -> Callable:
    """Census-dims Wide&Deep training trials (the bench entry's
    recipe: COO wide path + embedding bags + MLP, f32)."""
    import jax.numpy as jnp
    import numpy as np

    import bench
    from bigdl_tpu import nn
    from bigdl_tpu.models.recommender import WideAndDeep
    from bigdl_tpu.nn.sparse import COOBatch

    if smoke:
        batch, nnz_per, wide_dim, fields = 8, 2, 200, [20, 10]
        dense_dim, embed_dim, hidden, iters = 4, 4, (8,), 2
    else:
        batch, nnz_per, wide_dim = 8192, 8, 100_000
        fields = [10_000, 1_000, 100, 100, 50]
        dense_dim, embed_dim, hidden, iters = 13, 16, (100, 50), 8
    r = np.random.default_rng(3)
    nnz = batch * nnz_per
    coo = COOBatch(
        jnp.asarray(np.repeat(np.arange(batch, dtype=np.int32), nnz_per)),
        jnp.asarray(r.integers(0, wide_dim, nnz).astype(np.int32)),
        jnp.asarray(np.ones(nnz, np.float32)),
        (batch, wide_dim))
    deep_ids = jnp.asarray(np.stack(
        [r.integers(0, c, batch) for c in fields], axis=1).astype(np.int32))
    dense = jnp.asarray(r.normal(0, 1, (batch, dense_dim))
                        .astype(np.float32))
    yb = jnp.asarray(r.integers(0, 2, batch).astype(np.float32))

    class _SqueezeBCE:  # model emits (N, 1) logits->sigmoid
        def __init__(self):
            self.bce = nn.BCECriterion()

        def apply(self, out, y):
            return self.bce.apply(out[:, 0], y)

    def measure(trial, windows, rung):
        model = WideAndDeep(wide_dim, fields, dense_dim=dense_dim,
                            embed_dim=embed_dim, hidden=hidden,
                            kernel_impl=trial.get("kernel_impl"))
        samples, _ca, _path = bench._measure(
            model, batch, windows, iters,
            x=(coo, deep_ids, dense), y=yb, criterion=_SqueezeBCE(),
            compute_dtype=jnp.float32,
            fuse_k=trial.get("steps_per_dispatch", 1),
            warmup_windows=1,
            activation_memory=trial.get("activation_memory"))
        return samples

    return measure


def _serving_runner(smoke: bool) -> Callable:
    """Serving trials: the ``bench.py --serving`` closed-loop
    offered-load shape (T caller threads, single-row blocking predicts
    — occupancy earned purely by the batcher), one burst per window,
    rows/sec per burst as the sample."""
    import threading

    import numpy as np

    from bigdl_tpu import nn

    if smoke:
        din, n_threads, per_thread = 16, 4, 6
        model = nn.Sequential(nn.Linear(din, 32), nn.ReLU(),
                              nn.Linear(32, 8), nn.SoftMax())
    else:
        din, n_threads, per_thread = 64, 16, 100
        model = nn.Sequential(  # the bench --serving MLP
            nn.Linear(din, 256), nn.ReLU(), nn.Linear(256, 256),
            nn.ReLU(), nn.Linear(256, 8), nn.SoftMax())
    model.initialize(rng=0)
    spec = ((din,), np.float32)
    rng = np.random.default_rng(0)
    xs = [rng.normal(0, 1, (1, din)).astype(np.float32)
          for _ in range(n_threads)]

    def measure(trial, windows, rung):
        from bigdl_tpu.serving import InferenceService
        svc = InferenceService(
            model, input_spec=spec,
            max_batch_size=trial["serving_max_batch_size"],
            batch_timeout_ms=trial["serving_batch_timeout_ms"],
            buckets=trial.get("serving_row_buckets", ""),
            queue_capacity=4096,
            name=f"autotune-r{rung}")
        samples = []
        try:
            for _ in range(windows):
                barrier = threading.Barrier(n_threads + 1)
                errs: list = []

                def worker(x):
                    barrier.wait()
                    try:
                        for _ in range(per_thread):
                            svc.predict(x, timeout=120)
                    except Exception as e:  # recorded, never dropped
                        errs.append(f"{type(e).__name__}: {e}")

                threads = [threading.Thread(target=worker, args=(x,))
                           for x in xs]
                for t in threads:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                if errs:
                    raise RuntimeError(
                        f"serving trial {trial} failed: {errs[:3]}")
                samples.append(n_threads * per_thread / wall)
        finally:
            svc.stop()
        return samples

    return measure


def _int8_gemm_runner(smoke: bool) -> Callable:
    """Quantized-GEMM trials: raw ``ops.pallas_int8_gemm.int8_matmul``
    throughput on a serving-shaped panel (small batch, square
    128-multiple K/O so the kernel's ``supported()`` gate passes).
    The activation-mode knob is measurable on any backend — both modes
    lower to real XLA compute through the bitwise fallback (f32 MXU
    dot vs int8 quantize + int32 dot); the tile/impl knobs only change
    Mosaic behaviour and are tpu-gated below."""
    import jax
    import numpy as np

    from bigdl_tpu.ops.pallas_int8_gemm import int8_matmul

    if smoke:
        batch, k, o, iters = 8, 128, 128, 4
    else:
        batch, k, o, iters = 32, 512, 512, 50
    rng = np.random.default_rng(0)  # same data every trial
    x = np.asarray(rng.normal(0, 1, (batch, k)), np.float32)
    wq = rng.integers(-127, 128, (o, k)).astype(np.int8)
    ws = (rng.uniform(0.001, 0.02, (o, 1))).astype(np.float32)
    b = rng.normal(0, 1, (o,)).astype(np.float32)

    def measure(trial, windows, rung):
        mode = trial.get("int8_activation_mode", "weight_only")
        impl = trial.get("kernel_impl")
        block_rows = trial.get("int8_block_rows")

        @jax.jit
        def step(xin):
            return int8_matmul(xin, wq, ws, b, mode=mode, impl=impl,
                               block_rows=block_rows)

        step(x).block_until_ready()  # compile outside the window
        samples = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                y = step(x)
            y.block_until_ready()
            samples.append(iters * batch / (time.perf_counter() - t0))
        return samples

    return measure


# ----------------------------------------------------------- registry
_TRAINING_AXES = (
    Axis("steps_per_dispatch", (1, 2, 4, 8, 16)),
    Axis("activation_memory", ("none", "dots", "full")),
    Axis("kernel_impl", ("xla", "pallas"), requires="tpu",
         why="interpret-mode pallas on a non-TPU host is correctness "
             "emulation, not a perf signal (ops/PALLAS_NOTES.md); the "
             "knob keeps its config-chain default"),
    Axis("grad_wire_dtype", ("f32", "bf16"), requires="multidevice",
         why="wire compression only exists on a >1-chip data mesh; the "
             "single-chip bench harness cannot rank it"),
    Axis("grad_bucket_bytes", (1 << 20, 4 << 20, 16 << 20),
         requires="multidevice",
         why="bucketing only exists on a >1-chip data mesh; the "
             "single-chip bench harness cannot rank it"),
)
_TRAINING_SMOKE_AXES = (
    Axis("steps_per_dispatch", (1, 2)),
    Axis("activation_memory", ("none",)),
)

_SERVING_AXES = (
    Axis("serving_max_batch_size", (16, 32, 64)),
    Axis("serving_batch_timeout_ms", (0.0, 1.0, 2.0, 5.0)),
    Axis("serving_row_buckets", ("pow2", "top")),
)
_SERVING_SMOKE_AXES = (
    Axis("serving_max_batch_size", (8,)),
    Axis("serving_batch_timeout_ms", (0.0, 2.0)),
    Axis("serving_row_buckets", ("pow2",)),
)

_INT8_GEMM_AXES = (
    # measurable anywhere: both modes are real XLA compute through the
    # bitwise fallback (weight_only = f32 MXU dot against the int8
    # panel; dynamic = on-the-fly activation quantization + int32 dot)
    Axis("int8_activation_mode", ("weight_only", "dynamic")),
    Axis("kernel_impl", ("xla", "pallas"), requires="tpu",
         why="interpret-mode pallas on a non-TPU host is correctness "
             "emulation, not a perf signal (ops/PALLAS_NOTES.md); the "
             "knob keeps its config-chain default"),
    Axis("int8_block_rows", (0, 64, 128, 256), requires="tpu",
         why="the row-block tile only exists inside the Mosaic kernel; "
             "interpret-mode tiling on a non-TPU host times the "
             "emulator, not the MXU"),
)
_INT8_GEMM_SMOKE_AXES = (
    Axis("int8_activation_mode", ("weight_only", "dynamic")),
)

WORKLOADS: Dict[str, Workload] = {
    "ptb_lstm": Workload("ptb_lstm", "training", _TRAINING_AXES,
                         _TRAINING_SMOKE_AXES, _ptb_runner),
    "wide_deep": Workload("wide_deep", "training", _TRAINING_AXES,
                          _TRAINING_SMOKE_AXES, _wide_deep_runner),
    "serving_mlp": Workload("serving_mlp", "serving", _SERVING_AXES,
                            _SERVING_SMOKE_AXES, _serving_runner),
    "int8_gemm": Workload("int8_gemm", "kernel", _INT8_GEMM_AXES,
                          _INT8_GEMM_SMOKE_AXES, _int8_gemm_runner),
}


# ------------------------------------------------------------- output
def write_tuned(path: str, workload: str, backend: str, result: dict,
                provenance: dict) -> dict:
    """Merge one workload's winner into the tuned-configs file
    (atomic replace; other entries preserved).  An existing file that
    fails validation ABORTS the write — fix or delete it first; a
    damaged file must never be silently clobbered or extended."""
    from bigdl_tpu.utils import tuned
    entries: dict = {}
    if os.path.exists(path):
        with open(path, "r", encoding="utf-8") as fh:
            text = fh.read()
        if text.strip():
            entries = tuned.validate_document(json.loads(text))
    entries[f"{workload}@{backend}"] = {
        "workload": workload,
        "backend": backend,
        "best": result["best_config"],
        "provenance": provenance,
    }
    doc = {"schema_version": tuned.SCHEMA_VERSION, "entries": entries}
    tuned.validate_document(doc)  # never write what load() would reject
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return doc


def tune(workload: str, budget: int = 40, eta: int = 2,
         full_windows: int = 4, smoke: bool = False,
         out: Optional[str] = None, dry_run: bool = False,
         measure: Optional[Callable] = None) -> dict:
    """Search one workload's grid and (unless ``dry_run``) merge the
    winner into the tuned-configs file.  ``measure`` overrides the
    workload's runner (tests inject deterministic measurements)."""
    if workload not in WORKLOADS:
        raise SystemExit(
            f"unknown workload {workload!r}; available: "
            f"{sorted(WORKLOADS)}")
    if smoke and not dry_run and out is None:
        # a smoke winner comes from tiny models over a tiny grid —
        # merging it into the checked-in file would silently replace a
        # production-tuned entry under the same workload@backend key
        # (resolve_default never re-checks provenance.smoke).  Refused
        # BEFORE the search so no budget is spent on a doomed run.
        raise SystemExit(
            "--smoke results must not overwrite the default "
            "tuned_configs.json; pass an explicit --out (or --dry-run)")
    import jax

    import bench
    wl = WORKLOADS[workload]
    backend = jax.default_backend()
    axes = wl.smoke_axes if smoke else wl.axes
    axes, pruned = prune_axes(axes, backend, jax.device_count())
    for knob, why in pruned.items():
        logger.warning("axis %s pruned on %s: %s", knob, backend, why)
    grid = build_grid(axes)
    result = successive_halving(
        grid, measure or wl.runner(smoke), budget,
        eta=eta, full_windows=full_windows)
    result["workload"] = workload
    result["backend"] = backend
    result["pruned_axes"] = pruned
    result["smoke"] = smoke
    provenance = {
        "tool": "tools/autotune.py",
        "toolchain": bench._toolchain(),
        "score": result["score"],
        "score_metric": SCORE_METRIC,
        "n_configs": result["n_configs"],
        "windows_total": result["windows_total"],
        "budget": budget,
        "rungs": [{k: r[k] for k in
                   ("rung", "trials", "windows_per_trial", "survivors")}
                  for r in result["rungs"]],
        "excluded_windows": result["excluded_windows"],
        "pruned_axes": pruned,
        "smoke": smoke,
        "captured_at": time.strftime("%Y-%m-%d %H:%M:%S UTC",
                                     time.gmtime()),
    }
    if not dry_run:
        from bigdl_tpu.utils import tuned
        path = out or tuned.default_path()
        write_tuned(path, workload, backend, result, provenance)
        result["out"] = path
        # the process that just re-tuned must also SEE the new file
        tuned.reset_cache()
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.autotune",
        description="successive-halving autotuner over the declared "
                    "per-workload config grids; writes "
                    "tuned_configs.json (consumed by Engine/Config as "
                    "below-env defaults)")
    ap.add_argument("--workload", help="workload tag to tune")
    ap.add_argument("--budget", type=int, default=40,
                    help="HARD cap on total measured windows across "
                         "all rungs (default 40)")
    ap.add_argument("--eta", type=int, default=2,
                    help="halving factor (default 2)")
    ap.add_argument("--full-windows", type=int, default=4,
                    help="max windows per trial per rung — the "
                         "confirmation-run length (default 4)")
    ap.add_argument("--out", default=None,
                    help="tuned-configs path (default: "
                         "$BIGDL_TPU_TUNED_CONFIGS or the repo-root "
                         "tuned_configs.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny models + tiny grids (CI / tests); "
                         "requires --out or --dry-run — smoke winners "
                         "never overwrite the checked-in file")
    ap.add_argument("--dry-run", action="store_true",
                    help="search but do not write the tuned file")
    ap.add_argument("--list", action="store_true",
                    help="list workloads and their grids, then exit")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(name)s %(levelname)s %(message)s")
    if args.list:
        listing = {
            name: {"kind": wl.kind,
                   "axes": {ax.knob: list(ax.values) for ax in wl.axes},
                   "gated_axes": {ax.knob: ax.requires
                                  for ax in wl.axes if ax.requires}}
            for name, wl in sorted(WORKLOADS.items())}
        print(json.dumps(listing, indent=2))
        return 0
    if not args.workload:
        ap.error("--workload is required (or --list)")
    from bigdl_tpu.engine import Engine
    Engine.enable_compile_cache()
    result = tune(args.workload, budget=args.budget, eta=args.eta,
                  full_windows=args.full_windows, smoke=args.smoke,
                  out=args.out, dry_run=args.dry_run)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
