"""Benchmark driver — prints ONE JSON line.

Analog of the reference's throughput harness
``DL/models/utils/DistriOptimizerPerf.scala:56-140`` (synthetic-input
records/sec).  Measures a five-model menu on the local TPU chip, all
as full training steps (fwd+bwd+optimizer update): the two
BASELINE.json models — ResNet-50 and Inception-v1 (images/sec/chip) —
plus, since round 5, VGG-16 (images/sec; the conv-heavy regression
sentinel), the PTB "medium" LSTM (words/sec; the scan-heavy one), and
a census-dims Wide&Deep (records/sec; the sparse-embedding one —
COO wide features + embedding bags, the BASELINE.json recommender
config family).
ResNet-50 failing aborts the capture (it is the headline metric); a
failure in any secondary model records a ``<model>_error`` key and the
rest of the capture survives.

Config: NHWC, bf16 compute / f32 master params, batch 256, donated
buffers — best of the layout×batch×remat sweep on v5e (see git
history; batch 512 regresses ~6% past its own bandwidth floor from
memory pressure, FULL per-block remat costs ~20% because recomputed
convs re-read activations).

Integrity discipline (round-5, VERDICT r4 item 1):
- ``toolchain`` stamps jax/jaxlib versions + platform/device into every
  emitted JSON: r3→r4 showed cross-round numbers are toolchain-
  confounded (jax 0.8→0.9 moved ResNet's compiled step from 78.7 to
  ~85 GB/step with IDENTICAL source — a 5% throughput drop that is a
  compiler property, not a code property).
- AOT compile / cost-analysis failure is NEVER silent: the JSON either
  carries ``bottleneck`` + ``mfu`` or a ``cost_analysis_error`` string,
  and ``timing_path`` says whether the timing loop ran the AOT
  executable or fell back to jit dispatch.
- every measured window ends with a host sync that ASSERTS the loss is
  finite — a NaN-producing step can't post a throughput number.
- ``value`` is the MEDIAN over ``windows`` independent timing windows
  (the r4 definition); ``best_window`` is also reported as the bridge
  to r2/r3, whose ``value`` was best-of-4.

``bottleneck`` is TRACE-BACKED, not asserted: XLA's compiled-executable
cost analysis (flops + bytes accessed) gives the MXU-time and HBM-time
floors; the measured step time is compared against both.  ``mfu`` uses
the XLA-counted flops over the chip's published bf16 peak (``DEVICE_PEAKS``,
keyed by ``device_kind``; XLA counts 2 flops/MAC — the same convention
as the spec number).

Proof that the main path runs on the chip at all — and is right — is
``chip_smoke.py``, one process for the one chip; this file measures and
starts no child that needs the chip.

``dispatch_overhead_fraction`` (round-6): PTB-LSTM and Wide&Deep sit at
0.98/0.64 of their HBM floor yet posted 21.6%/24.0% window spread in r5
— their 3-9 ms steps are short enough that per-step host dispatch (and
the per-step ``float(loss)`` sync the old driver did) IS the gap.  The
bench now measures each of them twice — classic step-per-dispatch vs a
K=8 ``lax.scan``-fused block (the bench mirror of the driver's
``steps_per_dispatch``) — and reports
``1 - t_fused_step/t_unfused_step`` per model from the window medians
(negative values = fusion lost; never clamped).  Caveat recorded as
``*_cost_note``: XLA's cost analysis counts a scan body ONCE, so a
fused block's flops/bytes read as ≈ per-step, not per-block.

``collective_overhead_fraction`` (round-5, VERDICT r4 item 3): the r4
1-vs-8 "scaling efficiency" proxy measured cache effects (1.28 on one
core — physically meaningless as a collective gate).  Replaced by a
DIRECT ablation on the 8-device CPU mesh: the same shard_map DP
training step timed with the gradient all-reduce present vs ablated —
identical per-device compute, so the delta IS the collective cost.
Calibration notes (measured on this box, 2026-07-30): ResNet-20's
0.27M params make the psum invisible inside ±5% step noise, so the
workload is a deliberately param-heavy MLP (3×2048² ≈ 12.6M params,
50 MB/psum) where the host-emulated all-reduce is unambiguous.  Two
independent calibration runs: ablated 598/616 ms/step, with 879/866
(fraction 0.32/0.29), 3 injected extra all-reduces 1140/1123
(fraction 0.48/0.45).  Gate: fraction ≤ 0.38 — above the measured
band, below the injected band, ~2 extra all-reduces trip it — and a
SELF-TEST
run with the 3 extra all-reduces must itself VIOLATE the gate, proving
on every bench run that the gate can fail (VERDICT r4's "done"
criterion).  The absolute fraction is a property of the host-mesh
emulation (ICI is ~100× faster than host-memory loopback), so the
gate is a round-over-round regression tripwire, not an efficiency
claim; the real >60%-at-32-chips claim (whitepaper.md:160-164) needs
pod hardware.  The old 1-vs-8 number is kept informational only and
values > 1.05 are flagged ``measurement_error`` (super-linear
"scaling" on one physical core means cache effects dominate).

Round-7 (grad_sync wire formats): the collective entry now times the
explicit ``parallel/grad_sync.py`` step (bucketed reduce-scatter →
owned-slice update → all-gather) with f32 and bf16 wires alongside the
legacy psum modes, reporting ``collective_overhead_fraction_by_wire``
and each compiled child's ``collective_wire_bytes`` (per-op-kind
payload from ``tools.byte_audit.collective_wire_bytes``).  CPU-host
caveat, measured 2026-08-03: XLA's CPU backend CONVERTS sub-f32
collectives to f32 (a ``convert`` fusion brackets the reduce-scatter)
and host-emulates the stochastic-rounding RNG, so on this mesh the
bf16 wire shows f32 bytes and a ~2.4× slowdown — the numbers are
honest properties of the emulation, not of the wire format; the
bf16-halves-bytes invariant is gated on canned HLO in
``tests/test_byte_audit.py`` and the real effect needs the chip.
Also round-7: per-workload production ``steps_per_dispatch`` defaults
live in ``PRODUCTION_K`` (PTB-LSTM/Wide&Deep K=8, conv nets K=1 —
closes the ROADMAP K-defaults item), jittery entries discard 2 warmup
windows, and ``_stats`` adds a ``trimmed_median`` (min/max window
dropped) that derived fractions read.

Round-8 (telemetry): every ``_measure`` entry now reports
``*_pipeline_phases`` — host-dispatch vs pipeline-drain vs other time
shares from telemetry tracer spans over the measured windows — so
bottleneck attribution carries the pipeline picture alongside the
MXU/HBM floors; the 1v8 scaling child excludes compile/warmup and
unsteady (cache-effect/jitter) windows from its steady-state rate via
per-window spans and records the excluded fraction per mesh size
(``steady_state_filter`` — the r05 ``measurement_error`` fix: the flag
is still computed, but the number behind it is now auditable); the
serving sweep adds per-row-bucket latency (``latency_ms_by_bucket``).

Round-9 (checkpointing): ``bench.py --checkpoint`` runs the same small
training with checkpointing off / synchronous / async and records
``checkpoint_stall_fraction`` (driver-side checkpoint seconds over run
wall, from the ``checkpoint/stall_fraction`` registry gauge) plus
per-snapshot driver-stall and writer-commit times — the async path's
claim ("snapshots cost the driver a capture + enqueue, not a
serialize+CRC+fsync") as a recorded number (CPU smoke 2026-08-03:
sync 0.81 fraction / 330 ms per snapshot inline vs async 0.02 / 3.5
ms; the bitwise-inertness hard gate lives in tests/test_checkpoint.py).

Round-10 (fused kernels): ``ptb_lstm_fused_cell`` and
``wide_deep_fused_bag`` measure the two HBM-floor workloads with the
pallas custom kernels engaged (fused LSTM cell, fused COO
embedding-bag — ops/pallas_lstm.py, ops/pallas_embed.py) and
``fused_kernel_bytes`` records bytes/step + hbm_floor_fraction deltas
vs the XLA baselines.  CPU-host caveat, recorded 2026-08-03: off-TPU
the kernels run in pallas INTERPRET mode (XLA emulation of the kernel
body), so their throughput and cost-analysis numbers are
correctness-only, not perf — the strictly-lower-bytes claim is gated
on canned HLO in tests/test_byte_audit.py and the on-chip capture is
carried measurement debt.

Round-4 experiment log (all medians over ≥5 windows, v5e, batch 256;
r3 baseline ResNet-50 2499.7 img/s / 78.7 GB/step under jax 0.8,
Inception-v1 4645 / 37.3 GB/step):
- remat="tails" (save conv outputs, recompute BN/ReLU): 2160 img/s,
  bytes 92.5 GB — XLA's own saved-residual choice already beats the
  forced policy, and checkpoint boundaries block cross-block fusion.
- full per-block remat: ~20% slower (r3).
- batch 384: 2442 img/s, floor-fraction drops 0.94→0.84 (memory
  pressure); batch 512 worse still (r2).
- bf16 stochastic-rounded momentum: 2443 img/s, bytes 79.5 GB —
  optimizer state is 0.26% of step traffic; kept as a memory-capacity
  option (SGD state_dtype).
- maxpool backward (select-and-scatter) replacements: ablations show
  S&S wastes ~8.6 ms/step on Inception (pool-stubbed model runs at
  96.8% of its floor vs 82.6% real), but every alternative loses more:
  XLA phase decomposition 67.8 GB, pallas first-match kernel 80.4 GB
  (layout copies: pallas can't accept XLA's batch-minor layouts),
  hand-written custom-vjp 95.9 GB.  See nn/layers.py SpatialMaxPooling
  and ops/pallas_pool.py.
Round-5 log lives in BASELINE.md §"jax 0.9 floor shift".
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

import numpy as np

# round-1 recorded TPU v5 lite measurement (bf16, NCHW, batch 64); later
# rounds report improvement vs this anchor.  NOTE the anchor was taken
# under jax 0.8 — the `toolchain` stamp exists precisely because this
# ratio is toolchain-confounded across rounds.
BASELINE_IMAGES_PER_SEC = 1945.9  # 2026-07-29 r01
# Published peaks of ONE chip, keyed by jax's ``device_kind``, each with
# its source.  A device that is not in the table is an error, not a
# default: a roofline against another chip's peak is a wrong number.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "hbm_bytes_per_sec": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"'},
}


def device_peaks() -> dict:
    """Peaks of the device this process measures on."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {kind!r} — add it to "
            f"bench.DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


ROOT = os.path.dirname(os.path.abspath(__file__))

# Production steps_per_dispatch per workload (round-7, closes the
# ROADMAP "pick K defaults" item).  Chosen from the round-6
# dispatch_overhead_fraction ablation: PTB-LSTM (3-5 ms steps) and
# Wide&Deep (~9 ms) are host-dispatch-bound — K=8 recovers the
# measured per-step dispatch tax and is where the fused curve flattens
# (K=16 measured within noise of K=8 with 2× the staging latency at
# trigger boundaries).  The conv nets run 35-100 ms steps at 0.82-0.95
# of their HBM floor — dispatch is invisible there, and K>1 only
# delays trigger/validation boundaries, so they stay at K=1.
_HAND_TUNED_K = {
    "resnet50": 1, "inception_v1": 1, "vgg16": 1,
    "ptb_lstm": 8, "wide_deep": 8,
}


class _ProductionK(dict):
    """Deprecation shim (round-11, the autotuner PR): per-workload
    production ``steps_per_dispatch`` now prefers the autotuned
    ``tuned_configs.json`` entry for the live backend
    (``tools/autotune.py`` output, read through
    ``bigdl_tpu.utils.tuned``), falling back to the hand-maintained
    round-7 dict this object still carries.  ``PRODUCTION_K[w]`` keeps
    its historical int semantics; ``PRODUCTION_K.source(w)`` returns
    ``(k, "tuned_configs.json" | "hand")`` and the capture JSON records
    the source per entry (``dispatch_fuse_k_source``)."""

    def source(self, workload):
        try:
            from bigdl_tpu.utils.tuned import lookup
            v = lookup(workload, "steps_per_dispatch")
        except Exception:
            v = None  # tuned layer unavailable != bench unavailable
        if v is not None:
            return int(v), "tuned_configs.json"
        return dict.__getitem__(self, workload), "hand"

    def __getitem__(self, workload):
        return self.source(workload)[0]


PRODUCTION_K = _ProductionK(_HAND_TUNED_K)


def _toolchain():
    """Version/platform stamp embedded in every emitted JSON."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "unknown"),
    }


def _measure(model, batch: int, windows: int = 6, iters: int = 32,
             x=None, y=None, criterion=None, units_per_step=None,
             compute_dtype=None, fuse_k=None, warmup_windows: int = 0,
             activation_memory=None):
    """Compile + run one training step.

    Default inputs are the ImageNet-shaped NHWC batch; recurrent/other
    models pass explicit ``x``/``y``/``criterion``.  ``units_per_step``
    is the throughput numerator (images for conv nets, words for LMs;
    defaults to ``batch``).

    ``warmup_windows``: extra leading timing windows that run the full
    protocol (finite-loss assert included) but post no sample — the
    round-7 jitter fix for the short-step entries.

    ``activation_memory``: the remat slice of the driver's
    ``set_activation_memory`` policies — ``None``/``"none"`` (store
    everything), ``"dots"`` (save matmul outputs, recompute the
    elementwise chain) or ``"full"`` (save step inputs only), applied
    with the SAME ``jax.checkpoint`` policies the optimizer uses so
    autotuner trials measure the real knob.  The bf16 storage variants
    are expressed through ``compute_dtype`` here, not this arg.

    ``fuse_k``: fuse ``K`` consecutive steps into one jit dispatch via
    ``lax.scan`` over a K-stacked input — the bench-side mirror of the
    driver's ``steps_per_dispatch`` fusion.  The same batch is reused
    for every step of a block (timing, not learning), the per-step work
    is identical, and the reported units/s stay per ORIGINAL step, so
    unfused-vs-fused medians isolate the host dispatch overhead.

    Returns ``(per-window units/s list, cost-analysis dict,
    timing_path)`` where cost-analysis is either ``{"flops", "bytes"}``
    (≈ per step even for a fused block — XLA's cost analysis counts a
    scan body ONCE, so the block's totals are NOT divided by K; the
    caveat rides along as a ``note`` key / ``*_cost_note``) or
    ``{"error": <msg>}`` — never silently empty — and ``timing_path``
    records whether the timing loop ran the AOT executable or jit
    dispatch.  Raises if any measured window ends with a non-finite
    loss.
    """
    import jax
    import jax.numpy as jnp
    from functools import partial
    from bigdl_tpu import nn, optim
    from bigdl_tpu.utils.precision import mixed_precision_loss_fn

    criterion = criterion or nn.ClassNLLCriterion()
    units_per_step = units_per_step or batch
    method = optim.SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    params, mstate = model.init(jax.random.PRNGKey(0))
    ostate = method.init_state(params)
    if x is None:
        x = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (batch, 224, 224, 3)).astype(np.float32))
        y = jnp.asarray(np.random.default_rng(1).integers(
            0, 1000, (batch,)).astype(np.int32))

    base_loss = mixed_precision_loss_fn(model, criterion,
                                        compute_dtype or jnp.bfloat16)
    if activation_memory not in (None, "none"):
        if activation_memory not in ("dots", "full"):
            raise ValueError(
                f"activation_memory must be None|'none'|'dots'|'full' "
                f"here (bf16 storage rides compute_dtype), got "
                f"{activation_memory!r}")
        base_loss = jax.checkpoint(
            base_loss,
            policy=(jax.checkpoint_policies.dots_saveable
                    if activation_memory == "dots"
                    else jax.checkpoint_policies.nothing_saveable))
    grad_fn = jax.value_and_grad(base_loss, has_aux=True)
    rng0 = jax.random.PRNGKey(42)  # dropout rng (Inception-v1 trains one)

    if fuse_k:
        K = int(fuse_k)
        tstack = jax.tree_util.tree_map
        x = tstack(lambda a: jnp.stack([a] * K), x)
        y = tstack(lambda a: jnp.stack([a] * K), y)
        rngs0 = jnp.stack([rng0] * K)

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(p, ms, os_, xs, ys, lr, it0, rngs):
            def body(carry, inp):
                p, ms, os_ = carry
                xk, yk, itk, rngk = inp
                (loss, ms), g = grad_fn(p, ms, xk, yk, rngk)
                p, os_ = method.update(g, p, os_, lr, itk)
                return (p, ms, os_), loss
            its = it0 + jnp.arange(K, dtype=jnp.int32)
            (p, ms, os_), losses = jax.lax.scan(
                body, (p, ms, os_), (xs, ys, its, rngs))
            return p, ms, os_, losses[-1]

        rng0 = rngs0
        dispatches = max(1, iters // K)
        # XLA's compiled cost analysis counts a while/scan BODY once
        # (trip counts are not folded in — verified: an 8-fused block
        # reports the same flops as one unfused step), so the block's
        # numbers already read as ≈ per-step; do NOT divide by K.
        ca_note = ("scan body counted once by XLA cost analysis; "
                   "values are ~per-step, not per-block")
    else:
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(p, ms, os_, x, y, lr, it, rng):
            (loss, ms), g = grad_fn(p, ms, x, y, rng)
            p, os_ = method.update(g, p, os_, lr, it)
            return p, ms, os_, loss

        dispatches = iters
        ca_note = None
    steps_per_dispatch = iters // dispatches if not fuse_k else int(fuse_k)

    # ONE compile: the AOT executable serves both cost_analysis and the
    # timing loop (a separate jit dispatch would compile a second time).
    # Failure here is NOT allowed to be silent (VERDICT r4 weak#1: the
    # r4 BENCH capture lost mfu/bottleneck to an `except: pass`).
    run = step
    timing_path = "aot"
    try:
        compiled = step.lower(params, mstate, ostate, x, y, 0.1, 0,
                              rng0).compile()
        c = compiled.cost_analysis()
        if isinstance(c, list):
            c = c[0]
        ca = {"flops": float(c.get("flops", 0.0)),
              "bytes": float(c.get("bytes accessed", 0.0))}
        if ca_note:
            ca["note"] = ca_note
        run = compiled
    except Exception as e:
        if jax.devices()[0].platform == "tpu":
            # on the chip a step that cannot be AOT-compiled has no
            # honest timing: jit dispatch would compile it again (or
            # fail again) inside the measurement
            raise
        # off-chip callers (autotune smoke trials, tests) only want
        # samples; the failure is still recorded, never dropped
        ca = {"error": f"{type(e).__name__}: {e}"}
        timing_path = "jit_dispatch"

    # warmup; float() is a host round-trip, so it also waits
    params, mstate, ostate, loss = run(params, mstate, ostate, x, y,
                                       np.float32(0.1), np.int32(0), rng0)
    float(loss)

    # warmup-window discard (round-7): the first measured windows after
    # compile carry allocator/page-in noise — on the short-step entries
    # (PTB, Wide&Deep) that alone produced 0.22-0.24 rel_spread, enough
    # to drown a wire-compression delta.  Discarded windows run the
    # full timing protocol (finite-loss assert included) but never post
    # a sample.
    #
    # Pipeline-phase attribution (round-8, the telemetry PR): the
    # measured windows run under a telemetry tracer — span per dispatch
    # enqueue, span per end-of-window pipeline drain (the float(loss)
    # sync) — so each entry reports where its wall time went alongside
    # the MXU/HBM floors: ``dispatch`` is host enqueue time (including
    # backpressure when the in-flight queue is deep), ``device_wait``
    # the window-end drain, ``other`` device-bound time the host spent
    # inside neither.  Spans are two clock reads each — the timing
    # numbers are unchanged (the tracer is disabled during warmup too,
    # same discipline as the sample discard).
    from bigdl_tpu.telemetry import Tracer
    tracer = Tracer(enabled=False)
    samples = []
    wall_measured = 0.0
    for w in range(warmup_windows + windows):
        tracer.enabled = w >= warmup_windows
        t0 = time.perf_counter()
        for i in range(dispatches):
            with tracer.span("dispatch", cat="dispatch"):
                params, mstate, ostate, loss = run(
                    params, mstate, ostate, x, y, np.float32(0.1),
                    np.int32((w * dispatches + i) * steps_per_dispatch),
                    rng0)
        with tracer.span("device_wait", cat="device_wait"):
            lv = float(loss)  # full pipeline sync
        if not math.isfinite(lv):
            raise RuntimeError(
                f"non-finite loss {lv} at end of measured window {w} — "
                f"refusing to report a throughput number for a broken "
                f"computation")
        if w >= warmup_windows:
            dt = time.perf_counter() - t0
            wall_measured += dt
            samples.append(units_per_step * dispatches * steps_per_dispatch
                           / dt)
    if wall_measured > 0:
        totals = tracer.phase_totals()
        shares = {k: round(v / wall_measured, 4)
                  for k, v in sorted(totals.items())}
        shares["other"] = round(
            max(0.0, 1.0 - sum(shares.values())), 4)
        ca["pipeline_phases"] = shares
    return samples, ca, timing_path


def _stats(samples):
    med = statistics.median(samples)
    out = {
        "median": round(med, 1),
        "min": round(min(samples), 1),
        "max": round(max(samples), 1),
        "rel_spread": round((max(samples) - min(samples)) / med, 4),
        "windows": len(samples),
    }
    if len(samples) >= 5:
        # trimmed median (round-7): drop the single best and worst
        # window before taking the median — one outlier window (host
        # jitter on 3-9 ms steps) stops dragging the summary; derived
        # comparisons (dispatch_overhead_fraction) read this key
        trimmed = sorted(samples)[1:-1]
        out["trimmed_median"] = round(statistics.median(trimmed), 1)
    return med, out


UNSTEADY_TOL = 0.15  # relative deviation from the reference window rate


def steady_windows(samples, tol=UNSTEADY_TOL, min_samples=3):
    """The PR 6 steady-state window filter, shared by ``scaling_child``
    and ``tools/autotune.py`` (ONE implementation so the two exclusion
    accountings stay comparable): reference = trimmed median (single
    best/worst window dropped) at >= 3 samples, plain median below;
    kept = samples within ``tol`` relative deviation of the reference.

    Returns ``(kept, excluded, ref)``.  ``excluded`` is counted even
    when NOTHING survives — callers then score on ``ref``, never on a
    silently-unfiltered set.  Below ``min_samples`` the filter does not
    act (excluded = 0: one or two windows carry no spread to reason
    about; the autotuner raises this to 4 because its early rungs
    accumulate one window at a time)."""
    samples = list(samples)
    if len(samples) < min_samples:
        return samples, 0, (statistics.median(samples) if samples
                            else 0.0)
    ref = statistics.median(sorted(samples)[1:-1]) if len(samples) >= 3 \
        else statistics.median(samples)
    kept = [s for s in samples if abs(s - ref) <= tol * ref]
    return kept, len(samples) - len(kept), ref


def _bottleneck(ca, ips, batch, peak=None):
    """Roofline comparison of the measured step vs the compiled
    executable's XLA-counted flop and byte floors (``peak`` defaults to
    the device's published bf16 peak)."""
    peaks = device_peaks()
    peak = peak or peaks["bf16_flops"]
    step_ms = batch / ips * 1e3
    t_mxu = ca["flops"] / peak * 1e3
    t_hbm = ca["bytes"] / peaks["hbm_bytes_per_sec"] * 1e3
    return {
        "kind": "hbm" if t_hbm > t_mxu else "mxu",
        "xla_flops_G": round(ca["flops"] / 1e9, 1),
        "xla_bytes_GB": round(ca["bytes"] / 1e9, 2),
        "t_mxu_floor_ms": round(t_mxu, 2),
        "t_hbm_floor_ms": round(t_hbm, 2),
        "t_measured_ms": round(step_ms, 2),
        "hbm_floor_fraction": round(t_hbm / step_ms, 3),
    }


# ----------------------------------------------- collective overhead
def _cpu_mesh_env(n=8, **extra):
    """Env for a CPU-mesh child: strip any inherited device-count flag,
    then force an n-device host platform."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.update(extra)
    return env


def _collective_child_run(mode):
    """One collective-ablation child; returns the parsed JSON dict
    (``{"ms": ..., "wire_bytes": {...}}``) or None on failure."""
    out = subprocess_run([sys.executable, __file__, "--collective-child"],
                         env=_cpu_mesh_env(_BENCH_COLL_MODE=mode),
                         parse=json.loads)
    if out is not None and not isinstance(out, dict):
        print(f"collective child {mode}: non-dict output {out!r}",
              file=sys.stderr)
        return None
    return out


COLLECTIVE_GATE = 0.38  # calibration in module doc


def _collective_overhead():
    """Direct collective-cost ablation (module doc), round-7 extended to
    the grad_sync wire formats: alongside the legacy psum modes, two
    children run the explicit reduce-scatter → sharded-update →
    all-gather step of ``parallel/grad_sync.py`` with an f32 and a bf16
    wire, and every child reports its compiled program's bytes-on-wire
    from ``tools.byte_audit.collective_wire_bytes`` — so the JSON
    carries ``collective_overhead_fraction`` per wire dtype AND the
    payload reduction that explains it.  The legacy psum gate/self-test
    is unchanged; a failed grad_sync child records an error string
    without dropping the capture."""
    res = {}
    for mode in ("ablated", "with", "inject"):
        r = _collective_child_run(mode)
        if r is None:
            return None
        res[mode] = r
    gs_err = {}
    for mode in ("gs_f32", "gs_bf16"):
        r = _collective_child_run(mode)
        if r is None:
            gs_err[mode] = "grad_sync collective child failed"
        else:
            res[mode] = r
    t_abl = res["ablated"]["ms"]
    frac = lambda m: (res[m]["ms"] - t_abl) / res[m]["ms"]  # noqa: E731
    frac_inj = frac("inject")
    # self-test: the run with 3 injected extra all-reduces must itself
    # VIOLATE the gate — otherwise the gate has no discriminating power
    # and must read red regardless of the real fraction
    selftest = frac_inj > COLLECTIVE_GATE
    by_wire = {}
    for mode, wire in (("with", "psum_f32"), ("gs_f32", "f32"),
                       ("gs_bf16", "bf16")):
        if mode in res:
            by_wire[wire] = round(frac(mode), 4)
    out = {
        "collective_overhead_fraction": round(frac("with"), 4),
        "collective_overhead_fraction_by_wire": by_wire,
        "collective_step_ms": {k: round(v["ms"], 2)
                               for k, v in res.items()},
        "collective_wire_bytes": {k: v["wire_bytes"]
                                  for k, v in res.items()
                                  if v.get("wire_bytes")},
        "collective_gate_0p38": "pass"
                                if (selftest
                                    and frac("with") <= COLLECTIVE_GATE)
                                else "FAIL",
        "collective_selftest_injected_fraction": round(frac_inj, 4),
        "collective_selftest": "pass" if selftest else "FAIL",
    }
    if gs_err:
        out["collective_grad_sync_errors"] = gs_err
    return out


def _scaling_efficiency():
    """INFORMATIONAL 1-vs-8 virtual-CPU-mesh number (r4's proxy).  On
    one physical core this mostly measures cache effects — r4 recorded
    a physically-impossible 1.28 — so it no longer gates anything;
    values > 1.05 are flagged as measurement error.

    Round-8 (telemetry PR, ROADMAP item 4 "fix the scaling bench"): the
    child now measures per-window spans under the telemetry tracer and
    excludes compile/warmup windows plus unsteady outlier windows (the
    cache-effect / host-jitter windows that produced the impossible r05
    number) from the steady-state rate; the EXCLUDED FRACTION rides in
    the capture per mesh size, so any remaining flag is auditable —
    a high excluded fraction means the box couldn't produce a steady
    window and the ratio should not be trusted."""
    results = {}
    for n in (1, 8):
        out = subprocess_run([sys.executable, __file__, "--scaling-child"],
                             env=_cpu_mesh_env(_BENCH_SCALING_N=str(n)),
                             parse=json.loads)
        if out is None:
            return None
        results[n] = out
    value = round(results[8]["ips"] / results[1]["ips"], 3)
    return {
        "value": value,
        "measurement_error": value > 1.05,
        "images_per_sec": {str(n): round(v["ips"], 1)
                           for n, v in results.items()},
        "steady_state_filter": {
            str(n): {k: v[k] for k in ("windows_total", "windows_warmup",
                                       "windows_excluded",
                                       "excluded_fraction")}
            for n, v in results.items()},
    }


def subprocess_run(cmd, env, timeout=1200, parse=float):
    """Run a child, parse its last stdout line with ``parse`` (float for
    the legacy scalar children, ``json.loads`` for the collective
    children)."""
    import subprocess
    try:
        out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"child timed out after {timeout}s: {cmd}", file=sys.stderr)
        return None
    if out.returncode != 0:
        print(out.stderr[-2000:], file=sys.stderr)
        return None
    try:
        return parse(out.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        # a zero-exit child with unparseable stdout degrades to the
        # recorded-FAIL path, same as a crash (ADVICE r4 #4)
        print(f"unparseable child stdout: {out.stdout[-500:]!r}",
              file=sys.stderr)
        return None


def main(argv):
    from bigdl_tpu.models.resnet import resnet50
    from bigdl_tpu.models.inception import inception_v1

    smoke = "--smoke" in argv
    windows, iters = (1, 4) if smoke else (6, 32)
    batch = 256
    remat = "tails" if "--remat-tails" in argv else (
        True if "--remat-full" in argv else False)
    r_samples, r_ca, r_path = _measure(resnet50(format="NHWC", remat=remat),
                                       batch, windows, iters)
    r_ips, r_spread = _stats(r_samples)

    # bench-level registry (telemetry round 2): every workload's
    # measured pipeline-phase shares land here as gauges, and the
    # capture embeds the end-of-run scalars() snapshot under
    # "telemetry" — the same shape a /metrics scrape exports
    from bigdl_tpu.telemetry import MetricRegistry
    bench_reg = MetricRegistry()

    def _mirror_phases(prefix_, phases_):
        for cat, frac in (phases_ or {}).items():
            bench_reg.gauge(f"bench/{prefix_}_{cat}_fraction").set(frac)

    out = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(r_ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(r_ips / BASELINE_IMAGES_PER_SEC, 3),
        "best_window": round(max(r_samples), 1),  # r2/r3 metric bridge
        "spread": r_spread,
        "toolchain": _toolchain(),
        "timing_path": r_path,
        "config": f"NHWC/bf16/batch{batch}/donated"
                  + (f"/remat-{remat}" if remat else ""),
    }
    phases = r_ca.pop("pipeline_phases", None)
    if phases:
        out["pipeline_phases"] = phases
        _mirror_phases("resnet50", phases)
    if "error" in r_ca:
        out["cost_analysis_error"] = r_ca["error"]
    else:
        out["mfu"] = round(r_ips * (r_ca["flops"] / batch)
                           / device_peaks()["bf16_flops"], 4)
        out["bottleneck"] = _bottleneck(r_ca, r_ips, batch)
    if "--resnet-only" in argv:
        out["telemetry"] = bench_reg.scalars()
        print(json.dumps(out))
        return

    def emit(prefix, metric_key, samples, ca, path, units_per_step,
             peak=None):
        peak = peak or device_peaks()["bf16_flops"]
        ups, spread = _stats(samples)
        out[metric_key] = round(ups, 1)
        out[f"{prefix}_best_window"] = round(max(samples), 1)
        out[f"{prefix}_spread"] = spread
        phases = ca.pop("pipeline_phases", None)
        if phases:
            out[f"{prefix}_pipeline_phases"] = phases
            _mirror_phases(prefix, phases)
        if "error" in ca:
            out[f"{prefix}_cost_analysis_error"] = ca["error"]
        else:
            out[f"{prefix}_mfu"] = round(
                ups * (ca["flops"] / units_per_step) / peak, 4)
            out[f"{prefix}_bottleneck"] = _bottleneck(
                ca, ups, units_per_step, peak)
            if "note" in ca:
                out[f"{prefix}_cost_note"] = ca["note"]
        if path != "aot":
            out[f"{prefix}_timing_path"] = path

    def emit_guarded(prefix, metric_key, units_per_step, measure,
                     peak=None):
        """A secondary model's failure must not discard the primary
        metrics already measured (the r4 lost-capture failure mode)."""
        try:
            samples, ca, path = measure()
            emit(prefix, metric_key, samples, ca, path, units_per_step,
                 peak)
        except Exception as e:
            out[f"{prefix}_error"] = f"{type(e).__name__}: {e}"

    emit_guarded(
        "inception", "inception_v1_images_per_sec_per_chip", batch,
        lambda: _measure(inception_v1(format="NHWC"), batch, windows,
                         iters))

    # reference perf-driver menu breadth (DistriOptimizerPerf.scala:56-140
    # offers vgg16 alongside the conv nets; a recurrent model rounds out
    # the compiler-regression coverage: conv-heavy vs scan-heavy)
    import jax.numpy as jnp
    from bigdl_tpu import nn as _nn
    from bigdl_tpu.models.vgg import vgg16
    from bigdl_tpu.models.rnn import ptb_model

    # r5 config sweep: b128 1385 img/s (0.63 MFU), b256 1392 (0.634),
    # b64 965 (0.44), b128+scoped-vmem-32MiB 1310 — b128/default is the
    # knee; the ~37% over-MXU-floor residual (92 ms vs 58 ms floor,
    # HBM floor 46 ms) is imperfect MXU/DMA overlap on the giant
    # early-layer activations, stable across batch and vmem knobs
    v_batch = 128  # NCHW (the model's native layout; fc head at 7x7)
    rng = np.random.default_rng(2)
    vx = jnp.asarray(rng.normal(0, 1, (v_batch, 3, 224, 224))
                     .astype(np.float32))
    vy = jnp.asarray(rng.integers(0, 1000, (v_batch,)).astype(np.int32))
    emit_guarded(
        "vgg16", "vgg16_images_per_sec_per_chip", v_batch,
        lambda: _measure(vgg16(), v_batch, windows, iters, x=vx, y=vy))

    # PTB "medium" LSTM: vocab 10k, 650x2, seq 35, batch 20 — words/sec.
    # scan_unroll=5, chosen by the r5 sweeps (hoisted input projections
    # active in all rows): unroll 1 < {5, 7} consistently; 5 vs 7 are
    # within each other's spread; full unroll (35) loses loop-invariant
    # hoisting (bytes 1.58→3.32 GB) and regresses.  Pre-optimization
    # baseline (no hoist, no unroll): 31.3k words/s; optimized
    # measurements ranged 145k-280k median across host states.  This
    # number is host-dispatch sensitive (steps are ~3-5 ms): the 4x
    # iters below lengthen windows to ~0.6 s, and the reported spread
    # is the honesty mechanism — judge the number with it.
    p_batch, seq = 20, 35
    px = jnp.asarray(rng.integers(0, 10000, (p_batch, seq))
                     .astype(np.int32))
    py = jnp.asarray(rng.integers(0, 10000, (p_batch, seq))
                     .astype(np.int32))
    emit_guarded(
        "ptb_lstm", "ptb_lstm_words_per_sec_per_chip", p_batch * seq,
        # 4x iters: at ~5 ms/step a 32-iter window is only ~150 ms and
        # host jitter alone produced rel_spread 0.34; ~0.6 s windows
        # put the spread back in the same regime as the conv models.
        # warmup_windows=2: r5 still posted 0.216 rel_spread — the
        # first post-compile windows are the outliers (discard + the
        # trimmed median keep wire/fusion deltas above the noise)
        lambda: _measure(
            ptb_model(10000, 650, 650, 2, scan_unroll=5), p_batch,
            windows, iters * 4, x=px, y=py,
            criterion=_nn.TimeDistributedCriterion(
                _nn.ClassNLLCriterion()),
            units_per_step=p_batch * seq, warmup_windows=2))

    # dispatch-overhead ablation (round-6): the same step, fused via
    # lax.scan at the workload's PRODUCTION_K — the bench mirror of the
    # driver's steps_per_dispatch.  PTB (3-5 ms steps) and Wide&Deep
    # (~9 ms) are the two menu entries whose measured-vs-floor gap and
    # window spread are dominated by host dispatch, not hardware
    # (BENCH_r05: 21.6%/24.0% spread at 0.98/0.64 of floor); the fused
    # numbers quantify exactly that tax.
    emit_guarded(
        "ptb_lstm_fused", "ptb_lstm_fused_words_per_sec_per_chip",
        p_batch * seq,
        lambda: _measure(
            ptb_model(10000, 650, 650, 2, scan_unroll=5), p_batch,
            windows, iters * 4, x=px, y=py,
            criterion=_nn.TimeDistributedCriterion(
                _nn.ClassNLLCriterion()),
            units_per_step=p_batch * seq, fuse_k=PRODUCTION_K["ptb_lstm"],
            warmup_windows=2))

    # Wide&Deep sparse-embedding workload — the remaining BASELINE.json
    # config family (SparseTensor + embedding): COO wide features
    # through SparseLinear/segment-sum + embedding bags + MLP, census-
    # recipe dims at recommender batch.  f32 (lookup/bandwidth-bound;
    # bf16 buys nothing and would perturb the segment sums), so the
    # roofline peak is the v5e f32 matmul rate (~bf16 peak / 4 — moot
    # in practice: this workload's MXU floor is ~0 either way).
    # The 0.2-0.3 hbm_floor_fraction is the wide-table gradient's
    # random scatter (64K updates into 100K slots ≈ 3 ms measured
    # standalone) — a lowering cost the byte model doesn't see, same
    # class as Inception's S&S.  Alternatives measured WORSE on-chip
    # (r5): segment_sum(indices_are_sorted=True) 4.25 vs 3.91 ms on
    # the fwd path; sort+segsum weight-grad 4.29 vs scatter's 3.04 ms.
    # XLA's scatter is the best known formulation; revisit per
    # toolchain bump.
    wd_batch = 8192
    f32_peak = device_peaks()["bf16_flops"] / 4

    def _wide_deep_measure(fuse_k=None, kernel_impl=None, windows_=None,
                           iters_=None):
        from bigdl_tpu.models.recommender import WideAndDeep
        from bigdl_tpu.nn.sparse import COOBatch
        nnz_per = 8
        wide_dim, fields = 100_000, [10_000, 1_000, 100, 100, 50]
        m = WideAndDeep(wide_dim, fields, dense_dim=13, embed_dim=16,
                        hidden=(100, 50), kernel_impl=kernel_impl)
        r = np.random.default_rng(3)
        nnz = wd_batch * nnz_per
        coo = COOBatch(
            jnp.asarray(np.repeat(np.arange(wd_batch, dtype=np.int32),
                                  nnz_per)),
            jnp.asarray(r.integers(0, wide_dim, nnz).astype(np.int32)),
            jnp.asarray(np.ones(nnz, np.float32)),
            (wd_batch, wide_dim))
        deep_ids = jnp.asarray(np.stack(
            [r.integers(0, c, wd_batch) for c in fields],
            axis=1).astype(np.int32))
        dense = jnp.asarray(r.normal(0, 1, (wd_batch, 13))
                            .astype(np.float32))
        yb = jnp.asarray(r.integers(0, 2, wd_batch).astype(np.float32))

        class _SqueezeBCE:  # model emits (N, 1) logits->sigmoid
            def __init__(self):
                self.bce = _nn.BCECriterion()

            def apply(self, out, y):
                return self.bce.apply(out[:, 0], y)

        # 2x iters: ~9 ms/step needs ~0.6 s windows for a stable
        # median (same rationale as the PTB entry above)
        return _measure(m, wd_batch,
                        windows if windows_ is None else windows_,
                        iters * 2 if iters_ is None else iters_,
                        x=(coo, deep_ids, dense), y=yb,
                        criterion=_SqueezeBCE(),
                        compute_dtype=jnp.float32, fuse_k=fuse_k,
                        warmup_windows=2)

    emit_guarded("wide_deep", "wide_deep_records_per_sec_per_chip",
                 wd_batch, _wide_deep_measure,
                 peak=f32_peak)
    emit_guarded("wide_deep_fused", "wide_deep_fused_records_per_sec_per_chip",
                 wd_batch,
                 lambda: _wide_deep_measure(fuse_k=PRODUCTION_K["wide_deep"]),
                 peak=f32_peak)

    # fused custom kernels (round-10, the HBM-floor PR): the same two
    # memory-wall workloads with the pallas kernels engaged
    # (impl="pallas" — fused VMEM-resident LSTM cell, fused COO
    # embedding-bag; ops/pallas_lstm.py / ops/pallas_embed.py), vs
    # their XLA baselines above.  CPU-host caveat (also recorded in the
    # JSON): off-TPU these run under pallas INTERPRET mode — an XLA
    # emulation of the kernel body — so throughput AND cost-analysis
    # bytes are correctness-only, NOT perf; the strictly-lower
    # bytes/step claim is gated on canned step-program HLO in
    # tests/test_byte_audit.py, and the on-chip capture is carried
    # measurement debt (ROADMAP).  Off-TPU the entries run shortened
    # windows — they exist to record engagement + deltas, not timings.
    kernel_caveat = (
        "cpu-host interpret-mode pallas kernels: correctness-only "
        "numbers, not perf; on-chip bytes/step capture is carried "
        "measurement debt" if _toolchain()["platform"] != "tpu" else None)
    on_tpu = kernel_caveat is None
    k_windows = windows if on_tpu else min(windows, 2)
    k_iters = iters * 4 if on_tpu else max(2, iters // 8)
    emit_guarded(
        "ptb_lstm_fused_cell",
        "ptb_lstm_fused_cell_words_per_sec_per_chip", p_batch * seq,
        lambda: _measure(
            ptb_model(10000, 650, 650, 2, scan_unroll=5,
                      kernel_impl="pallas"), p_batch,
            k_windows, k_iters, x=px, y=py,
            criterion=_nn.TimeDistributedCriterion(
                _nn.ClassNLLCriterion()),
            units_per_step=p_batch * seq, warmup_windows=1))
    emit_guarded(
        "wide_deep_fused_bag",
        "wide_deep_fused_bag_records_per_sec_per_chip", wd_batch,
        lambda: _wide_deep_measure(kernel_impl="pallas",
                                   windows_=k_windows,
                                   iters_=k_iters),
        peak=f32_peak)
    if kernel_caveat:
        out["fused_kernel_caveat"] = kernel_caveat
    # bytes/step + hbm_floor_fraction deltas, XLA baseline vs pallas
    # (from each entry's compiled cost analysis)
    fkb = {}
    for name_, base_p, fused_p in (
            ("ptb_lstm", "ptb_lstm", "ptb_lstm_fused_cell"),
            ("wide_deep", "wide_deep", "wide_deep_fused_bag")):
        bb = out.get(f"{base_p}_bottleneck")
        fb = out.get(f"{fused_p}_bottleneck")
        if bb and fb:
            fkb[name_] = {
                "bytes_per_step_GB_xla": bb["xla_bytes_GB"],
                "bytes_per_step_GB_pallas": fb["xla_bytes_GB"],
                "bytes_delta_GB": round(
                    fb["xla_bytes_GB"] - bb["xla_bytes_GB"], 2),
                "hbm_floor_fraction_xla": bb["hbm_floor_fraction"],
                "hbm_floor_fraction_pallas": fb["hbm_floor_fraction"],
            }
    out["fused_kernel_bytes"] = fkb if fkb else None

    # dispatch_overhead_fraction = 1 - t_fused_step / t_unfused_step,
    # from the TRIMMED window medians when available (negative = fusion
    # lost — also worth knowing; never clamped).  This is the measured
    # per-step host dispatch tax the K-step driver loop removes.
    def _metric(prefix, key):
        spread = out.get(f"{prefix}_spread", {})
        return spread.get("trimmed_median") or out.get(key)

    dof = {}
    for name_, base_k, fused_k in (
            ("ptb_lstm", "ptb_lstm_words_per_sec_per_chip",
             "ptb_lstm_fused_words_per_sec_per_chip"),
            ("wide_deep", "wide_deep_records_per_sec_per_chip",
             "wide_deep_fused_records_per_sec_per_chip")):
        base_v = _metric(name_, base_k)
        fused_v = _metric(f"{name_}_fused", fused_k)
        if base_v and fused_v:
            dof[name_] = round(1.0 - base_v / fused_v, 4)
    out["dispatch_overhead_fraction"] = dof if dof else None
    # dispatch_fuse_k_source (round-11): where each workload's fused-K
    # came from — the autotuned tuned_configs.json entry for this
    # backend, or the hand-maintained round-7 dict the shim falls back
    # to (bench.PRODUCTION_K deprecation shim).
    fuse_src = {w: PRODUCTION_K.source(w)
                for w in ("ptb_lstm", "wide_deep")}
    out["dispatch_fuse_k"] = {w: k for w, (k, _) in fuse_src.items()}
    out["dispatch_fuse_k_source"] = {w: s
                                     for w, (_, s) in fuse_src.items()}

    if not smoke:
        co = _collective_overhead()
        if co is not None:
            out.update(co)
        else:
            out["collective_overhead_fraction"] = None
            out["collective_gate_0p38"] = "FAIL"
            out["collective_error"] = "collective child subprocess failed"
        sc = _scaling_efficiency()
        if sc is not None:
            out["scaling_1v8_informational"] = sc
        else:
            out["scaling_1v8_informational"] = {
                "value": None, "error": "scaling child failed"}
    out["telemetry"] = bench_reg.scalars()
    print(json.dumps(out))


def scaling_child():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bigdl_tpu import nn, optim
    from bigdl_tpu.models.resnet import resnet_cifar

    n = int(os.environ["_BENCH_SCALING_N"])
    devs = jax.devices()
    assert len(devs) >= n, (n, devs)
    mesh = Mesh(np.array(devs[:n]), ("data",))

    model = resnet_cifar(depth=20)
    criterion = nn.ClassNLLCriterion()
    method = optim.SGD(learning_rate=0.1, momentum=0.9)
    params, mstate = model.init(jax.random.PRNGKey(0))
    ostate = method.init_state(params)
    batch = 128  # FIXED global batch: same total work for every n
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (batch, 3, 32, 32)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (batch,)).astype(np.int32))
    data_sh = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    x = jax.device_put(x, data_sh)
    y = jax.device_put(y, data_sh)
    params = jax.tree_util.tree_map(lambda a: jax.device_put(a, repl), params)
    mstate = jax.tree_util.tree_map(lambda a: jax.device_put(a, repl), mstate)
    ostate = jax.tree_util.tree_map(lambda a: jax.device_put(a, repl), ostate)

    def loss_fn(p, ms, x, y):
        out, ms2 = model.apply(p, ms, x, training=True)
        return criterion.apply(out, y), ms2

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, ms, os_, x, y, it):
        (loss, ms), g = grad_fn(p, ms, x, y)
        p, os_ = method.update(g, p, os_, 0.1, it)
        return p, ms, os_, loss

    # warmup discipline matching the main measurement (VERDICT r4 weak#6)
    for w in range(2):
        params, mstate, ostate, loss = step(params, mstate, ostate, x, y, w)
    loss.block_until_ready()

    # steady-state window filter (telemetry PR; the r05
    # measurement_error fix): every window runs under a tracer span so
    # the capture is auditable, then (a) the first WARM_WINDOWS are
    # excluded as compile/allocator/page-in warmup, (b) remaining
    # windows whose rate deviates >UNSTEADY_TOL from the trimmed median
    # are excluded as unsteady (host jitter, cache effects — on one
    # physical core these produced the physically-impossible r05
    # super-linear "scaling").  The excluded fraction is REPORTED, not
    # hidden: a box that can't produce steady windows shows it.
    from bigdl_tpu.telemetry import Tracer
    WARM_WINDOWS = 2
    tracer = Tracer(enabled=True)
    iters = 10
    for w in range(WARM_WINDOWS + 6):
        t0ns = time.perf_counter_ns()
        for i in range(iters):
            params, mstate, ostate, loss = step(params, mstate, ostate,
                                                x, y, 2 + w * iters + i)
        loss.block_until_ready()
        t1ns = time.perf_counter_ns()
        tracer.record("window", t0ns, t1ns, cat="measure",
                      rate=round(batch * iters / ((t1ns - t0ns) / 1e9),
                                 1),
                      warmup=w < WARM_WINDOWS)
    # decisions read back from the SPANS (the trace is the audit trail)
    spans = [(e[6]["rate"], e[6]["warmup"]) for e in tracer.events()
             if e[1] == "window"]
    steady = [r for r, warm in spans if not warm]
    # excluded_fraction is over the STEADY CANDIDATES only — warmup
    # windows are excluded by design on every run and would put a
    # constant floor under the "couldn't hold steady" signal
    kept, excluded, ref = steady_windows(steady)
    print(json.dumps({
        "ips": statistics.median(kept) if kept else ref,
        "windows_total": len(spans),
        "windows_warmup": len(spans) - len(steady),
        "windows_excluded": excluded,
        "excluded_fraction": round(excluded / max(1, len(steady)), 4),
    }))


def collective_child():
    """Time one sharded DP training step with the gradient all-reduce
    present ("with"), ablated ("ablated" — identical per-device compute,
    gradients simply left unreduced so each device trains locally), with
    3 extra all-reduces ("inject" — the gate's self-test), or through
    the explicit grad_sync protocol ("gs_f32"/"gs_bf16" — bucketed
    reduce-scatter in the wire dtype, owned-slice update, all-gather).
    The model is the framework's own Sequential MLP sized param-heavy
    (module-doc calibration) so the collective is visible above step
    noise.  Prints one JSON line: ``{"ms": <median ms/step>,
    "wire_bytes": <byte_audit per-collective payload>}``."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bigdl_tpu import nn, optim
    from bigdl_tpu.parallel import grad_sync as gs
    from tools.byte_audit import collective_wire_bytes

    mode = os.environ["_BENCH_COLL_MODE"]
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ("data",))
    n = 8

    D = 2048
    model = (nn.Sequential()
             .add(nn.Linear(D, D)).add(nn.Tanh())
             .add(nn.Linear(D, D)).add(nn.Tanh())
             .add(nn.Linear(D, D)))
    criterion = nn.MSECriterion()
    method = optim.SGD(learning_rate=0.01, momentum=0.9)
    params, mstate = model.init(jax.random.PRNGKey(0))
    batch = 64  # 8/device
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (batch, D)).astype(np.float32))
    y = jnp.asarray(rng.normal(0, 1, (batch, D)).astype(np.float32))

    def loss_fn(p, ms, x, y):
        out, ms2 = model.apply(p, ms, x, training=True)
        return criterion.apply(out, y), ms2

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    psum = lambda t: jax.tree_util.tree_map(
        lambda a: lax.psum(a, "data"), t)

    repl = jax.tree_util.tree_map(lambda _: P(), params)
    replm = jax.tree_util.tree_map(lambda _: P(), mstate)

    if mode.startswith("gs_"):
        wire = {"gs_f32": jnp.float32, "gs_bf16": jnp.bfloat16}[mode]
        from bigdl_tpu.utils.config import get_config
        plan = gs.build_plan(params, n, get_config().grad_bucket_bytes)
        ostate = gs.init_state(plan, params, method)

        def one_step(p, ms, os_, x, y, it):
            (loss, ms2), g = grad_fn(p, ms, x, y)
            p2, os2 = gs.sync_and_update(plan, g, os_, method, 0.1, it,
                                         wire_dtype=wire,
                                         axis_name="data")
            return p2, ms2, os2, loss[None]

        os_spec = jax.tree_util.tree_map(lambda _: P("data"), ostate)
    else:
        ostate = method.init_state(params)

        def one_step(p, ms, os_, x, y, it):
            (loss, ms2), g = grad_fn(p, ms, x, y)
            if mode in ("with", "inject"):
                g = psum(g)
            if mode == "inject":
                g = psum(psum(psum(g)))  # 3 artificial extra all-reduces
            p2, os2 = method.update(g, p, os_, 0.1, it)
            return p2, ms2, os2, loss[None]

        os_spec = jax.tree_util.tree_map(lambda _: P(), ostate)

    # place inputs to match the specs BEFORE lowering: the AOT
    # executable binds the argument shardings it was lowered with
    place = lambda t, spec: jax.tree_util.tree_map(
        lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)), t, spec)
    params = place(params, repl)
    mstate = place(mstate, replm)
    ostate = place(ostate, os_spec)
    x = jax.device_put(x, NamedSharding(mesh, P("data")))
    y = jax.device_put(y, NamedSharding(mesh, P("data")))

    # replication checking off: in "ablated" mode params are
    # legitimately device-varying (that is the point of the ablation)
    fn = jax.jit(gs.shard_map_unchecked(
        one_step, mesh,
        in_specs=(repl, replm, os_spec, P("data"), P("data"), P()),
        out_specs=(repl, replm, os_spec, P("data"))),
        donate_argnums=(0, 1, 2))
    # AOT compile: the executable serves the timing loop AND exposes
    # the optimized HLO for the bytes-on-wire audit
    compiled = fn.lower(params, mstate, ostate, x, y,
                        np.int32(0)).compile()
    try:
        wire_bytes = collective_wire_bytes(compiled.as_text())
    except Exception as e:  # audit is best-effort; timing must survive
        wire_bytes = {"error": f"{type(e).__name__}: {e}"}
    for i in range(3):  # warmup
        params, mstate, ostate, loss = compiled(params, mstate, ostate,
                                                x, y, np.int32(i))
    loss.block_until_ready()
    meds = []
    for w in range(3):
        iters = 5
        t0 = time.perf_counter()
        for i in range(iters):
            params, mstate, ostate, loss = compiled(
                params, mstate, ostate, x, y, np.int32(3 + w * iters + i))
        loss.block_until_ready()
        meds.append((time.perf_counter() - t0) / iters * 1e3)
    print(json.dumps({"ms": statistics.median(meds),
                      "wire_bytes": wire_bytes}))


def serving_bench(smoke: bool = False):
    """Offered-load sweep over the ``bigdl_tpu.serving`` engine.

    Closed-loop load: T caller threads each issue single-row blocking
    ``predict`` calls back-to-back (the worst coalescing case — every
    request is 1 row, so occupancy is earned purely by the batcher).
    Per load point: rows/sec, p50/p95/p99 latency, mean batch occupancy,
    and dispatches-per-request (1/T is perfect coalescing at T ≤
    max_batch).  A fresh service per point keeps stats windows clean;
    warmup (AOT bucket compiles) happens before the timed window, and
    any steady-state compile is RECORDED as a gate failure — per-point
    ``recompile_gate: FAIL`` plus top-level
    ``serving_recompile_gate: FAIL`` — following the bench's
    record-never-abort discipline (same shape as
    ``collective_gate_0p38``); the hard assertion lives in
    ``tests/test_serving.py``.
    """
    import threading as _threading

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceService

    din, n_threads_sweep = 64, (1, 4, 16, 64)
    per_thread = 25 if smoke else 200
    model = nn.Sequential(
        nn.Linear(din, 256), nn.ReLU(), nn.Linear(256, 256), nn.ReLU(),
        nn.Linear(256, 8), nn.SoftMax())
    model.initialize(rng=0)
    spec = ((din,), np.float32)
    rng = np.random.default_rng(0)

    out = {"metric": "serving_throughput_rows_per_sec",
           "unit": "rows/sec", "toolchain": _toolchain(),
           "config": f"mlp{din}x256x256x8/max_batch32/timeout2ms/"
                     f"single-row-closed-loop", "sweep": []}
    best = 0.0
    for n_threads in n_threads_sweep:
        svc = InferenceService(model, input_spec=spec, max_batch_size=32,
                               batch_timeout_ms=2.0, queue_capacity=4096,
                               name=f"bench-load{n_threads}")
        warm_compiles = svc.compile_count
        xs = [rng.normal(0, 1, (1, din)).astype(np.float32)
              for _ in range(n_threads)]
        barrier = _threading.Barrier(n_threads + 1)
        errs = []

        def worker(x):
            barrier.wait()
            try:
                for _ in range(per_thread):
                    svc.predict(x, timeout=120)
            except Exception as e:  # recorded, never dropped
                errs.append(f"{type(e).__name__}: {e}")

        threads = [_threading.Thread(target=worker, args=(x,))
                   for x in xs]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = svc.stats()
        svc.stop()
        n_req = n_threads * per_thread
        point = {
            "offered_threads": n_threads,
            "requests": n_req,
            "throughput_rps": round(n_req / wall, 1),
            "latency_ms": stats["latency_ms"],
            # per-row-bucket latency windows (ROADMAP 1c): which bucket
            # pays the p99 — a 1-row dispatch and a 32-row bucket have
            # very different service times the global window hides
            "latency_ms_by_bucket": stats["latency_ms_by_bucket"],
            "mean_batch_occupancy": stats["mean_batch_occupancy"],
            "dispatch_count": stats["dispatch_count"],
            "dispatches_per_request":
                round(stats["dispatch_count"] / n_req, 4),
            "steady_state_compiles": svc.compile_count - warm_compiles,
            # end-of-run registry snapshot (telemetry round 2): the
            # capture carries the numbers a /metrics scrape would have
            # seen, so bench output and the admin plane agree by
            # construction
            "telemetry": svc.metrics.registry.scalars(),
        }
        if errs:
            point["errors"] = errs[:3]
        if svc.compile_count != warm_compiles:
            point["recompile_gate"] = "FAIL"  # GL106-for-serving tripped
        out["sweep"].append(point)
        best = max(best, point["throughput_rps"])
    out["value"] = best
    out["serving_recompile_gate"] = (
        "FAIL" if any(p.get("recompile_gate") == "FAIL"
                      for p in out["sweep"]) else "PASS")
    from bigdl_tpu.serving import row_buckets
    out["serving_buckets"] = list(row_buckets(32))
    # admin-plane scrape overhead: the SAME closed-loop load twice — once
    # with a 1 Hz /metrics scraper hitting a live AdminServer, once
    # without — so the exporter's cost on tail latency is a measured
    # number, not a claim.  Rendering runs on the scraper's thread; the
    # expected delta is ~0 (the hot path never touches the admin plane),
    # and any real regression shows up as p99_scraped - p99_baseline.
    out["admin_scrape_overhead"] = _admin_scrape_overhead(
        model, spec, rng, smoke)
    # wire mode (ISSUE 14): the SAME model behind the HTTP frontend vs
    # in-process submit → wire_overhead_ms, plus the zero-dropped-
    # requests gate through 3 hot deploys under sustained wire load
    out["wire"] = _wire_bench(model, spec, rng, smoke)
    out["wire_zero_drop_gate"] = out["wire"]["zero_drop_gate"]
    # connection-scalability sweep (ISSUE 19): idle flood + active mix
    # on the event-loop core vs the threaded baseline
    out["connection_sweep"] = _connection_sweep(model, spec, rng, smoke)
    # int8 quantized speed path (the int8 serving PR): the SAME model
    # served f32 / bf16-params / int8-quantized (kernel-backed,
    # ops/pallas_int8_gemm.py) under the same closed-loop load —
    # throughput, p50/p99, occupancy, bytes/step from compiled cost
    # analysis, and the quantized_speedup ratio
    out["quantized"] = _quantized_serving_bench(model, spec, rng, smoke)
    out["quantized_speedup"] = out["quantized"].get("quantized_speedup")
    if out["quantized"].get("caveat"):
        out["quantized_kernel_caveat"] = out["quantized"]["caveat"]
    # continuous-batching decode column (ISSUE 20): mixed-length
    # autoregressive generate sweep through a DecodeService —
    # tokens/sec, TTFT, inter-token latency, batch occupancy — vs the
    # static-batch (wave-barriered) baseline schedule
    out["decode"] = _decode_serving_bench(smoke)
    out["decode_continuous_vs_static_speedup"] = out["decode"].get(
        "continuous_vs_static_speedup")
    if out["decode"].get("caveat"):
        out["decode_cpu_caveat"] = out["decode"]["caveat"]
    return out


def _wire_bench(model, spec, rng, smoke: bool) -> dict:
    """Loopback closed-loop HTTP clients vs in-process predicts on the
    same deployed model.  Reports client-side p50/p99 for both paths
    and their delta (``wire_overhead_ms`` — the HTTP hop: JSON
    round-trip, admission, dispatch).  TCP connect/handshake is timed
    EXPLICITLY per connection and reported as ``connect_latency_ms``
    instead of letting http.client's lazy connect fold it into the
    first request's latency (the ISSUE-19 sweep fix — handshake cost
    scales with accept-path pressure, per-request cost with dispatch
    pressure; mixing them hid both).  Then holds the offered load
    while 3 :class:`~bigdl_tpu.frontend.HotCutover` deploys run;
    every wire request must come back 200 with the bitwise-expected
    output (every version serves the same params, so correctness is
    exact).  Record-never-abort: the gate FAILs in the capture, the
    hard assert lives in ``tests/test_frontend.py``."""
    import http.client
    import threading as _threading

    import numpy as np

    from bigdl_tpu.frontend import FrontendServer, HotCutover
    from bigdl_tpu.serving import ModelRegistry

    n_threads = 4 if smoke else 8
    per_thread = 25 if smoke else 100
    din = spec[0][0]

    reg = ModelRegistry()
    svc = reg.deploy("wire", model, input_spec=spec, max_batch_size=32,
                     batch_timeout_ms=2.0, queue_capacity=4096)
    fe = FrontendServer(reg, port=0)
    fe.start()
    xs = [rng.normal(0, 1, (1, din)).astype(np.float32)
          for _ in range(n_threads)]
    expected = [np.asarray(model.apply(svc.params, svc.state, x,
                                       training=False)[0])
                for x in xs]

    def wire_load(tag, deploys=0):
        """Closed-loop wire clients (one keep-alive connection per
        thread); optionally run hot deploys from the main thread while
        the load holds.  Returns (lat_ms list, connect_ms list, bad
        list, reports)."""
        lats, conn_lats, bad = [], [], []
        barrier = _threading.Barrier(n_threads + 1)
        bodies = [json.dumps({"inputs": x.tolist()}).encode()
                  for x in xs]

        def worker(t):
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=120)
            barrier.wait()
            my_lats = []
            try:
                # explicit timed connect: handshake cost reported on
                # its own, never folded into request latency
                t0 = time.perf_counter()
                conn.connect()
                conn_lats.append((time.perf_counter() - t0) * 1e3)
                for _ in range(per_thread):
                    t0 = time.perf_counter()
                    conn.request("POST", "/v1/models/wire/predict",
                                 body=bodies[t],
                                 headers={"Content-Type":
                                          "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    my_lats.append((time.perf_counter() - t0) * 1e3)
                    if resp.status != 200:
                        bad.append(f"{tag}: HTTP {resp.status}")
                        continue
                    got = np.asarray(
                        json.loads(payload)["outputs"], np.float32)
                    # allclose, not bitwise: a wire request coalesces
                    # into whatever row bucket the moment offers, and
                    # bucket executables differ in fusion order by a
                    # last ulp (the documented resilience-bench
                    # concession; the BITWISE wire gate at fixed
                    # bucket lives in tests/test_frontend.py)
                    if not np.allclose(got, expected[t],
                                       rtol=1e-5, atol=1e-7):
                        bad.append(f"{tag}: wrong output thread {t}")
            except Exception as e:
                bad.append(f"{tag}: {type(e).__name__}: {e}")
            finally:
                conn.close()
            lats.extend(my_lats)

        threads = [_threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        barrier.wait()
        reports = []
        if deploys:
            cut = HotCutover(reg, fe)
            try:
                for _ in range(deploys):
                    reports.append(cut.deploy(
                        "wire", model, max_batch_size=32,
                        batch_timeout_ms=2.0, queue_capacity=4096))
            except Exception as e:
                # recorded (fails the gate), never aborts — and the
                # worker threads below still get joined
                bad.append(f"{tag}: deploy failed: "
                           f"{type(e).__name__}: {e}")
        for th in threads:
            th.join()
        return lats, conn_lats, bad, reports

    def inproc_load():
        lats = []
        barrier = _threading.Barrier(n_threads + 1)

        def worker(t):
            barrier.wait()
            my_lats = []
            for _ in range(per_thread):
                t0 = time.perf_counter()
                reg.predict("wire", xs[t], timeout=120)
                my_lats.append((time.perf_counter() - t0) * 1e3)
            lats.extend(my_lats)

        threads = [_threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        barrier.wait()
        for th in threads:
            th.join()
        return lats

    def pct(samples, q):
        s = sorted(samples)
        return round(s[min(len(s) - 1,
                           max(0, int(round(q * len(s))) - 1))], 3)

    # discarded warmup (first-run jit/socket/thread-pool costs), then
    # the measured pair on warm state.  Record-never-abort: a cutover
    # drain timeout (slow/loaded host) or any phase error lands in the
    # gate as FAIL — it must not kill the whole serving bench nor leak
    # the frontend/registry into later sections
    bad, reports = [], []
    wire_lat = inproc_lat = cut_lat = wire_conn = [0.0]
    try:
        wire_load("warmup")
        inproc_load()
        wire_lat, wire_conn, wire_bad, _ = wire_load("steady")
        inproc_lat = inproc_load()
        # 3 hot deploys under sustained wire load: the zero-drop gate
        cut_lat, _cut_conn, cut_bad, reports = wire_load("cutover",
                                                         deploys=3)
        bad = wire_bad + cut_bad
    except Exception as e:
        bad.append(f"wire bench phase error: {type(e).__name__}: {e}")
    out = {
        "offered_threads": n_threads,
        "requests_per_phase": n_threads * per_thread,
        "wire_latency_ms": {"p50": pct(wire_lat, 0.50),
                            "p99": pct(wire_lat, 0.99)},
        "connect_latency_ms": {"p50": pct(wire_conn, 0.50),
                               "p99": pct(wire_conn, 0.99)},
        "inproc_latency_ms": {"p50": pct(inproc_lat, 0.50),
                              "p99": pct(inproc_lat, 0.99)},
        "wire_overhead_ms": {
            "p50": round(pct(wire_lat, 0.50) - pct(inproc_lat, 0.50), 3),
            "p99": round(pct(wire_lat, 0.99) - pct(inproc_lat, 0.99), 3)},
        "cutover_latency_ms": {"p50": pct(cut_lat, 0.50),
                               "p99": pct(cut_lat, 0.99)},
        "hot_deploys": len(reports),
        "cutovers": [{k: r[k] for k in ("old_version", "new_version",
                                        "warmup_s", "wire_drain_s")}
                     for r in reports],
        "bad_responses": len(bad),
        "zero_drop_gate": "PASS" if not bad else "FAIL",
        "frontend_telemetry": fe.metrics.scalars(),
    }
    if bad:
        out["errors"] = bad[:5]
    fe.stop()
    reg.stop_all()
    return out


# idle-connection holder, run as a SUBPROCESS: N parked sockets in
# this process would double-bill the fd budget (server side + client
# side), capping the sweep at half the rlimit.  Prints "READY <open>
# <errors>" once all connects resolve, holds until stdin closes.
_IDLE_CHILD_SRC = r"""
import socket, sys, time
port, n = int(sys.argv[1]), int(sys.argv[2])
socks, errs = [], 0
for i in range(n):
    try:
        socks.append(socket.create_connection(("127.0.0.1", port),
                                              timeout=60))
    except OSError:
        errs += 1
    if i % 512 == 511:
        time.sleep(0.05)  # let the accept loop drain the backlog
sys.stdout.write("READY %d %d\n" % (len(socks), errs))
sys.stdout.flush()
sys.stdin.readline()
for s in socks:
    try:
        s.close()
    except OSError:
        pass
"""


def _connection_sweep(model, spec, rng, smoke: bool) -> dict:
    """Connection-count scalability sweep (ISSUE 19, ROADMAP item 2):
    park N idle keep-alive connections on the frontend, then run a
    closed-loop active mix through them and record p50/p99, connect
    latency, throughput and the server's own open-connection count.
    The event-loop core sweeps to 10k idle; the threaded baseline
    stops at 1k (a 10k-thread point would measure the OS scheduler,
    not the wire plane — and that asymmetry IS the result).

    Record-never-abort: any point that fails (EMFILE, connect
    timeout, refused) records an ``error`` field and the sweep moves
    on to the next point."""
    import http.client
    import subprocess
    import sys as _sys
    import threading as _threading

    import numpy as np

    from bigdl_tpu.frontend import FrontendServer
    from bigdl_tpu.serving import ModelRegistry

    din = spec[0][0]
    n_threads = 4 if smoke else 8
    per_thread = 10 if smoke else 50
    points = ([("eventloop", 0), ("eventloop", 200),
               ("threaded", 0), ("threaded", 200)] if smoke else
              [("eventloop", 0), ("eventloop", 1000),
               ("eventloop", 10000),
               ("threaded", 0), ("threaded", 1000)])
    xs = [rng.normal(0, 1, (1, din)).astype(np.float32)
          for _ in range(n_threads)]
    bodies = [json.dumps({"inputs": x.tolist()}).encode() for x in xs]

    def pct(samples, q):
        s = sorted(samples) or [0.0]
        return round(s[min(len(s) - 1,
                           max(0, int(round(q * len(s))) - 1))], 3)

    def active_mix(port):
        """One closed-loop burst; returns (lats, connect_ms, bad,
        wall_s)."""
        lats, conn_ms, bad = [], [], []
        barrier = _threading.Barrier(n_threads + 1)

        def worker(t):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            barrier.wait()
            my = []
            try:
                t0 = time.perf_counter()
                conn.connect()
                conn_ms.append((time.perf_counter() - t0) * 1e3)
                for _ in range(per_thread):
                    t0 = time.perf_counter()
                    conn.request("POST", "/v1/models/wire/predict",
                                 body=bodies[t],
                                 headers={"Content-Type":
                                          "application/json"})
                    resp = conn.getresponse()
                    resp.read()
                    my.append((time.perf_counter() - t0) * 1e3)
                    if resp.status != 200:
                        bad.append(f"HTTP {resp.status}")
            except Exception as e:
                bad.append(f"{type(e).__name__}: {e}")
            finally:
                conn.close()
            lats.extend(my)

        threads = [_threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        t_wall = time.perf_counter()
        barrier.wait()
        for th in threads:
            th.join()
        return lats, conn_ms, bad, time.perf_counter() - t_wall

    out = {"idle_holder": "subprocess",
           "active_threads": n_threads,
           "requests_per_point": n_threads * per_thread,
           "points": []}
    for core, idle in points:
        point = {"core": core, "idle_target": idle}
        reg = fe = child = None
        try:
            reg = ModelRegistry()
            reg.deploy("wire", model, input_spec=spec,
                       max_batch_size=32, batch_timeout_ms=2.0,
                       queue_capacity=4096)
            # uncapped + no reaper: the sweep measures coexistence
            # with the idle flood, not the cap refusing it
            fe = FrontendServer(reg, port=0, core=core,
                                max_connections=0, idle_timeout_s=0.0)
            fe.start()
            if idle:
                child = subprocess.Popen(
                    [_sys.executable, "-c", _IDLE_CHILD_SRC,
                     str(fe.port), str(idle)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True)
                ready = (child.stdout.readline() or "").split()
                opened = int(ready[1]) if ready[:1] == ["READY"] else 0
                point["idle_open"] = opened
                point["idle_connect_errors"] = (
                    int(ready[2]) if len(ready) > 2 else idle - opened)
                deadline = time.monotonic() + 120
                while (fe.open_connections < opened
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
            active_mix(fe.port)  # warmup (jit + thread pools)
            lats, conn_ms, bad, wall = active_mix(fe.port)
            point.update({
                "open_connections": fe.open_connections,
                "latency_ms": {"p50": pct(lats, 0.50),
                               "p99": pct(lats, 0.99)},
                "connect_ms": {"p50": pct(conn_ms, 0.50),
                               "p99": pct(conn_ms, 0.99)},
                "throughput_rps": (round(len(lats) / wall, 1)
                                   if wall > 0 else 0.0),
                "bad_responses": len(bad),
            })
            if bad:
                point["errors"] = bad[:3]
        except Exception as e:
            point["error"] = f"{type(e).__name__}: {e}"
        finally:
            if child is not None:
                try:
                    child.stdin.write("\n")
                    child.stdin.flush()
                    child.wait(timeout=60)
                except Exception:
                    child.kill()
            if fe is not None:
                try:
                    fe.stop()
                except Exception:
                    pass
            if reg is not None:
                try:
                    reg.stop_all()
                except Exception:
                    pass
        out["points"].append(point)
    sustained = [p.get("idle_open", 0) for p in out["points"]
                 if p["core"] == "eventloop" and "error" not in p
                 and p.get("bad_responses", 1) == 0]
    out["max_idle_sustained_eventloop"] = max(sustained, default=0)
    return out


def _quantized_serving_bench(model, spec, rng, smoke: bool) -> dict:
    """int8-vs-bf16-vs-f32 serving column (the int8 speed-path PR).

    The SAME bench MLP behind three :class:`InferenceService` variants:
    f32 params (baseline), params cast to bf16, and the int8-quantized
    twin (``nn.quantized.quantize``, weight-only mode, ``impl="pallas"``
    so the ops/pallas_int8_gemm.py path engages — only its
    supported() shapes, here the aligned 256x256 middle layer; the odd
    edge layers take the bitwise XLA fallback, which is the realistic
    mixed deployment).  Per variant: closed-loop throughput_rps,
    p50/p99, mean occupancy, the service's ``weights_dtype`` tag, and
    bytes/step from the compiled fixed-batch forward's cost analysis.
    ``quantized_speedup`` = int8 rps / f32 rps.

    Record-never-abort: any variant failure is captured in its entry.
    CPU-host caveat (recorded like ``fused_kernel_caveat``): off-TPU
    the int8 kernel runs under pallas INTERPRET mode, so throughput
    and cost-analysis bytes are correctness-only, NOT perf — the
    strictly-lower-bytes weight-panel claim is gated on canned HLO in
    ``tests/test_byte_audit.py``, and the load is shortened to
    engagement-proof size.
    """
    import threading as _threading

    import numpy as np

    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.quantized import quantize as _quantize
    from bigdl_tpu.serving import InferenceService

    din = spec[0][0]
    on_tpu = _toolchain()["platform"] == "tpu"
    caveat = None if on_tpu else (
        "cpu-host interpret-mode int8 kernel: throughput and "
        "cost-analysis bytes are correctness-only, not perf; "
        "shortened load")
    n_threads = (4 if smoke else 8) if on_tpu else 2
    per_thread = (25 if smoke else 100) if on_tpu else 10

    model._ensure_init()
    bf16_params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 else a, model._params)
    try:
        qmodel = _quantize(model, mode="weight_only", impl="pallas")
    except Exception as e:  # recorded below per-variant, never aborts
        qmodel, q_err = None, f"{type(e).__name__}: {e}"
    else:
        q_err = None

    variants = [
        ("f32", model, model._params, model._state),
        ("bf16", model, bf16_params, model._state),
        ("int8", qmodel, None, None),
    ]
    out = {"int8_mode": "weight_only", "caveat": caveat,
           "offered_threads": n_threads,
           "requests_per_variant": n_threads * per_thread}

    def _bytes_per_step(m_, params, state):
        """Compiled cost-analysis bytes of one fixed 32-row forward."""
        xb = jnp.asarray(rng.normal(0, 1, (32, din)).astype(np.float32))

        def fwd(p, s, a):
            return m_.apply(p, s, a, training=False)[0]

        compiled = jax.jit(fwd).lower(params, state, xb).compile()
        c = compiled.cost_analysis()
        if isinstance(c, list):
            c = c[0]
        return float(c.get("bytes accessed", 0.0))

    for tag, m_, p_, s_ in variants:
        entry = {}
        try:
            if m_ is None:
                raise RuntimeError(q_err or "quantize failed")
            svc = InferenceService(m_, p_, s_, input_spec=spec,
                                   max_batch_size=32,
                                   batch_timeout_ms=2.0,
                                   queue_capacity=4096,
                                   name=f"bench-q-{tag}")
            try:
                xs = [rng.normal(0, 1, (1, din)).astype(np.float32)
                      for _ in range(n_threads)]
                barrier = _threading.Barrier(n_threads + 1)
                errs = []

                def worker(x):
                    barrier.wait()
                    try:
                        for _ in range(per_thread):
                            svc.predict(x, timeout=120)
                    except Exception as e:  # recorded, never dropped
                        errs.append(f"{type(e).__name__}: {e}")

                threads = [_threading.Thread(target=worker, args=(x,))
                           for x in xs]
                for t in threads:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                stats = svc.stats()
                lat = stats["latency_ms"] or {}
                entry = {
                    "throughput_rps": round(
                        n_threads * per_thread / wall, 1),
                    "latency_ms": {"p50": lat.get("p50"),
                                   "p99": lat.get("p99")},
                    "mean_batch_occupancy":
                        stats["mean_batch_occupancy"],
                    "weights_dtype": stats.get("weights_dtype", "f32"),
                }
                if errs:
                    entry["errors"] = errs[:3]
            finally:
                svc.stop()
            # params/state as the SERVICE resolved them (the quantized
            # twin re-owns its buffers; init gave empty params)
            entry["bytes_per_step"] = _bytes_per_step(
                m_, svc.params, svc.state)
        except Exception as e:  # record-never-abort
            entry["error"] = f"{type(e).__name__}: {e}"
        out[tag] = entry

    f32_rps = out.get("f32", {}).get("throughput_rps")
    int8_rps = out.get("int8", {}).get("throughput_rps")
    out["quantized_speedup"] = (round(int8_rps / f32_rps, 3)
                                if f32_rps and int8_rps else None)
    fb = out.get("f32", {}).get("bytes_per_step")
    ib = out.get("int8", {}).get("bytes_per_step")
    out["bytes_per_step_ratio_int8_vs_f32"] = (
        round(ib / fb, 3) if fb and ib else None)
    return out


def _decode_serving_bench(smoke: bool) -> dict:
    """Continuous-batching autoregressive decode column (ISSUE 20).

    Offered-load sweep of mixed-length generate requests through ONE
    :class:`DecodeService` (a 2-layer toy LM; the service AOT-compiles
    its step + prefill executables once, before any timed window).
    Closed-loop clients call ``submit(..., on_token=...)`` so TTFT
    (submit → first token) and inter-token gaps are measured at the
    CALLER, per request.  Per load point: tokens/sec, TTFT p50/p99,
    inter-token p50/p99, and window batch occupancy computed from
    stats deltas (step-tokens over slot-steps — admission-emitted
    first tokens excluded, they aren't step work).

    ``static_batch`` is the baseline column: the SAME request mix
    submitted in synchronized waves of ``slots`` requests, each wave
    barriered on its slowest sequence before the next is offered —
    exactly what batch-level (non-iteration-level) scheduling does to
    a decode fleet.  ``continuous_vs_static_speedup`` = continuous
    tokens/sec at matched offered load / static tokens/sec.

    Record-never-abort: any failure lands in the capture as
    ``error``.  CPU-host caveat (recorded like
    ``quantized_kernel_caveat``): off-TPU the per-step dispatch
    overhead of a toy LM dominates, so absolute tokens/sec and the
    speedup ratio are schedule-shape evidence, not TPU perf.
    """
    import threading as _threading

    import numpy as np

    from bigdl_tpu.models.transformer import transformer_lm
    from bigdl_tpu.serving import DecodeService

    on_tpu = _toolchain()["platform"] == "tpu"
    caveat = None if on_tpu else (
        "cpu-host decode: per-step dispatch overhead dominates a "
        "2-layer toy LM, so tokens/sec and the continuous-vs-static "
        "ratio are schedule-shape evidence, not TPU perf; "
        "shortened load")
    slots = 4
    max_new = 4 if smoke else 8
    per_client = 2 if smoke else 6
    lens = (2, 4, 6, 9, 12)
    out = {"unit": "tokens/sec", "slots": slots,
           "max_new_tokens": max_new, "prompt_lens": list(lens),
           "caveat": caveat, "sweep": []}
    try:
        model = transformer_lm(vocab_size=64, embed_dim=32,
                               num_heads=4, num_layers=2,
                               max_len=64).initialize(0)
        dec = DecodeService(model, slots=slots, max_seq_len=32,
                            max_prompt_len=12, prefill_buckets="top",
                            queue_capacity=4096, name="bench-decode")
    except Exception as e:  # record-never-abort
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    rng = np.random.default_rng(7)

    def mk_prompts(n):
        return [rng.integers(1, 64,
                             size=lens[i % len(lens)]).tolist()
                for i in range(n)]

    def snap():
        d = dec.stats()["decode"]
        return (d["steps"], d["tokens_generated"], d["admissions"])

    def run_requests(prompts, ttfts, gaps, errs, lock):
        """Closed loop over ``prompts`` on the calling thread."""
        for p in prompts:
            marks = []
            t0 = time.perf_counter()
            fut = dec.submit(p, max_new_tokens=max_new,
                             on_token=lambda i, t, m=marks:
                                 m.append(time.perf_counter()))
            try:
                fut.result(timeout=300)
            except Exception as e:  # recorded, never dropped
                with lock:
                    errs.append(f"{type(e).__name__}: {e}")
                continue
            with lock:
                if marks:
                    ttfts.append((marks[0] - t0) * 1e3)
                    gaps.extend((b - a) * 1e3 for a, b in
                                zip(marks, marks[1:]))

    def pcts(xs):
        if not xs:
            return None
        a = np.asarray(xs)
        return {"p50": round(float(np.percentile(a, 50)), 3),
                "p99": round(float(np.percentile(a, 99)), 3)}

    def window(steps0, tok0, adm0):
        steps1, tok1, adm1 = snap()
        dsteps = steps1 - steps0
        step_tokens = (tok1 - tok0) - (adm1 - adm0)
        occ = (round(step_tokens / (dsteps * slots), 4)
               if dsteps else None)
        return (tok1 - tok0), occ

    try:
        # warm pass: first-token + step executables already AOT-compile
        # in the ctor, but run one request end-to-end so the timed
        # windows never see a cold scheduler thread
        dec.generate(mk_prompts(1)[0], max_new_tokens=2)

        cont_tps_at = {}
        for n_clients in (2, 8):
            point = {"offered_clients": n_clients,
                     "requests": n_clients * per_client}
            try:
                ttfts, gaps, errs = [], [], []
                lock = _threading.Lock()
                client_prompts = [mk_prompts(per_client)
                                  for _ in range(n_clients)]
                barrier = _threading.Barrier(n_clients + 1)

                def worker(ps):
                    barrier.wait()
                    run_requests(ps, ttfts, gaps, errs, lock)

                threads = [_threading.Thread(target=worker, args=(ps,))
                           for ps in client_prompts]
                for t in threads:
                    t.start()
                s0 = snap()
                barrier.wait()
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                toks, occ = window(*s0)
                point.update({
                    "tokens_per_sec": round(toks / wall, 1),
                    "ttft_ms": pcts(ttfts),
                    "inter_token_ms": pcts(gaps),
                    "batch_occupancy": occ,
                })
                if errs:
                    point["errors"] = errs[:3]
                cont_tps_at[n_clients] = point["tokens_per_sec"]
            except Exception as e:  # record-never-abort
                point["error"] = f"{type(e).__name__}: {e}"
            out["sweep"].append(point)

        # static-batch baseline: waves of `slots` requests, every wave
        # barriered on its slowest sequence (offered load matches the
        # slots-saturating sweep point: 8 clients over 4 slots offers
        # a full wave the moment the previous one clears)
        static = {"wave_size": slots}
        try:
            n_waves = max(1, (8 * per_client) // slots)
            ttfts, gaps, errs = [], [], []
            lock = _threading.Lock()
            waves = [mk_prompts(slots) for _ in range(n_waves)]
            static["requests"] = n_waves * slots
            s0 = snap()
            t0 = time.perf_counter()
            for wave in waves:
                ws = [_threading.Thread(
                    target=run_requests,
                    args=([p], ttfts, gaps, errs, lock))
                    for p in wave]
                for t in ws:
                    t.start()
                for t in ws:
                    t.join()  # the wave barrier: slowest gates all
            wall = time.perf_counter() - t0
            toks, occ = window(*s0)
            static.update({
                "tokens_per_sec": round(toks / wall, 1),
                "ttft_ms": pcts(ttfts),
                "inter_token_ms": pcts(gaps),
                "batch_occupancy": occ,
            })
            if errs:
                static["errors"] = errs[:3]
        except Exception as e:  # record-never-abort
            static["error"] = f"{type(e).__name__}: {e}"
        out["static_batch"] = static

        st = dec.stats()["decode"]
        out["step_ms_ewma"] = st["step_ms_ewma"]
        out["cumulative_step_occupancy"] = st["step_occupancy"]
        c_tps = cont_tps_at.get(8)
        s_tps = static.get("tokens_per_sec")
        out["continuous_vs_static_speedup"] = (
            round(c_tps / s_tps, 3) if c_tps and s_tps else None)
    except Exception as e:  # record-never-abort
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        try:
            dec.stop(drain=False, timeout=5)
        except Exception:
            pass
    return out


def _admin_scrape_overhead(model, spec, rng, smoke: bool) -> dict:
    import threading as _threading
    import urllib.request

    import numpy as np

    from bigdl_tpu.serving import InferenceService
    from bigdl_tpu.telemetry.admin import AdminServer

    n_threads = 4 if smoke else 16
    per_thread = 25 if smoke else 150
    din = spec[0][0]

    def run_load(scrape: bool):
        svc = InferenceService(
            model, input_spec=spec, max_batch_size=32,
            batch_timeout_ms=2.0, queue_capacity=4096,
            name=f"bench-scrape-{'on' if scrape else 'off'}")
        srv = None
        stop = _threading.Event()
        scrapes = [0]
        if scrape:
            srv = AdminServer(port=0)
            srv.add_registry(svc.name, svc.metrics.registry)
            srv.start()

            def scraper():
                while not stop.is_set():
                    try:
                        urllib.request.urlopen(
                            srv.url("/metrics"), timeout=5).read()
                        scrapes[0] += 1
                    except Exception:
                        pass  # recorded via scrape count staying low
                    stop.wait(1.0)  # the 1 Hz cadence

            _threading.Thread(target=scraper, daemon=True).start()
        xs = [rng.normal(0, 1, (1, din)).astype(np.float32)
              for _ in range(n_threads)]
        barrier = _threading.Barrier(n_threads + 1)

        def worker(x):
            barrier.wait()
            for _ in range(per_thread):
                svc.predict(x, timeout=120)

        threads = [_threading.Thread(target=worker, args=(x,))
                   for x in xs]
        for t in threads:
            t.start()
        barrier.wait()
        for t in threads:
            t.join()
        stop.set()
        stats = svc.stats()
        if srv is not None:
            srv.stop()
        svc.stop()
        return stats["latency_ms"], scrapes[0]

    # discarded warmup load: the FIRST run in the process pays jit/
    # allocator/thread-pool warmup; without it the baseline-then-
    # scraped order would bias the delta toward understating the
    # scrape cost (the scraped run would inherit warm state)
    run_load(scrape=False)
    base_lat, _ = run_load(scrape=False)
    scraped_lat, n_scrapes = run_load(scrape=True)
    return {
        "offered_threads": n_threads,
        "requests": n_threads * per_thread,
        "scrapes": n_scrapes,
        "p99_ms_baseline": base_lat["p99"] if base_lat else None,
        "p99_ms_scraped": scraped_lat["p99"] if scraped_lat else None,
        "p99_overhead_ms": (
            round(scraped_lat["p99"] - base_lat["p99"], 3)
            if base_lat and scraped_lat else None),
    }


def resilience_bench(smoke: bool = False):
    """Availability under replica failure (``--resilience``): the
    ``--serving`` offered-load shape pointed at a 4-replica
    :class:`~bigdl_tpu.resilience.ReplicaSet` while a seeded fault plan
    kills one replica's batcher thread mid-sweep.

    Per load point the capture records the full degradation story:
    requests accounted one-by-one (ok / shed / deadline / error — an
    accepted request that never resolves would show up as a hang and
    fail the ``lost`` gate), wrong-answer count against a precomputed
    expected output (must be 0 — a failover must never fabricate rows;
    compared with allclose because a request may coalesce into any row
    bucket and bucket executables differ in fusion order by a last-ulp
    — the same concession ``test_serving.py`` makes across dispatch
    sizes; the bitwise gate at fixed bucket lives in
    ``tests/test_resilience.py``),
    throughput and p99 split into baseline / degraded (quarantine
    window) / recovered phases from a health-state monitor thread, and
    the ``resilience/*`` counters (death, quarantine, failovers,
    revival, probes, readmission) straight from the registry.  The
    acceptance shape — throughput degrades to ~(N-1)/N rather than
    zero and the replica re-admits after probation — is gated hard in
    ``tests/test_resilience.py``; this entry records the measured
    numbers (record-never-abort) so availability joins the bench
    trajectory.
    """
    import threading as _threading

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.resilience import ReplicaSet
    from bigdl_tpu.resilience.faults import FaultInjector
    from bigdl_tpu.resilience.health import HealthPolicy

    din, n_replicas = 64, 4
    run_s = 2.5 if smoke else 6.0
    kill_after = 10 if smoke else 30  # replica-0 dispatch index floor
    model = nn.Sequential(
        nn.Linear(din, 256), nn.ReLU(), nn.Linear(256, 256), nn.ReLU(),
        nn.Linear(256, 8), nn.SoftMax())
    model.initialize(rng=0)
    spec = ((din,), np.float32)
    rng = np.random.default_rng(0)

    out = {"metric": "serving_availability_under_replica_kill",
           "unit": "fraction", "toolchain": _toolchain(),
           "config": f"mlp{din}x256x256x8/{n_replicas}replicas/"
                     f"kill_r0_after{kill_after}/run{run_s}s",
           "sweep": []}
    for n_threads in ((4,) if smoke else (4, 16)):
        plan = f"replica_death@target=0,after={kill_after},count=1"
        rs = ReplicaSet(
            model, n_replicas=n_replicas, input_spec=spec,
            max_batch_size=32, batch_timeout_ms=2.0,
            queue_capacity=4096, name=f"bench-resil{n_threads}",
            deadline_ms=5000.0, max_retries=2,
            health=HealthPolicy(probe_backoff_s=0.4),
            fault_injector=FaultInjector(plan, seed=0))
        x = rng.normal(0, 1, (1, din)).astype(np.float32)
        expected = np.asarray(rs.predict(x, timeout=30))
        counts = {"ok": 0, "shed": 0, "deadline": 0, "error": 0,
                  "wrong": 0}
        errs: list = []
        records = []  # (t_done, latency_s) of successes
        lock = _threading.Lock()
        stop_at = [0.0]
        barrier = _threading.Barrier(n_threads + 2)

        def worker():
            from bigdl_tpu.serving import (DeadlineExceeded,
                                           ServiceOverloaded)
            barrier.wait()
            while time.monotonic() < stop_at[0]:
                t0 = time.monotonic()
                try:
                    got = rs.predict(x, timeout=2.0)
                except ServiceOverloaded as e:
                    with lock:
                        counts["shed"] += 1
                    wait = e.retry_after_ms or 5.0
                    time.sleep(min(wait, 50.0) / 1e3)
                    continue
                except (DeadlineExceeded, TimeoutError):
                    with lock:
                        counts["deadline"] += 1
                    continue
                except Exception as e:  # recorded, never dropped
                    with lock:
                        counts["error"] += 1
                        errs.append(f"{type(e).__name__}: {e}")
                    continue
                t1 = time.monotonic()
                good = np.allclose(np.asarray(got), expected,
                                   rtol=1e-5, atol=1e-7)
                with lock:
                    counts["ok" if good else "wrong"] += 1
                    records.append((t1, t1 - t0))

        timeline = []  # (t, health_states) sampled by the monitor

        def monitor():
            barrier.wait()
            while time.monotonic() < stop_at[0]:
                timeline.append((time.monotonic(), rs.health_states()))
                time.sleep(0.02)

        threads = [_threading.Thread(target=worker)
                   for _ in range(n_threads)]
        threads.append(_threading.Thread(target=monitor))
        for t in threads:
            t.start()
        # stop_at must be valid BEFORE the barrier releases: workers
        # check it immediately after their own barrier.wait() returns,
        # possibly before this thread runs another statement
        stop_at[0] = time.monotonic() + run_s
        barrier.wait()
        t_start = time.monotonic()
        for t in threads:
            t.join()
        stats = rs.stats()
        rs.stop()

        # phase boundaries from the sampled health timeline
        t_dead = next((t for t, h in timeline if "quarantined" in h),
                      None)
        t_readmit = next(
            (t for t, h in timeline
             if t_dead is not None and t > t_dead
             and all(s == "healthy" for s in h)), None)

        def phase_stats(lo, hi):
            done = [(t, lat) for t, lat in records if lo <= t < hi]
            if not done or hi <= lo:
                return {"rps": 0.0, "p99_ms": None, "n": len(done)}
            lats = sorted(lat for _, lat in done)
            p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))]
            return {"rps": round(len(done) / (hi - lo), 1),
                    "p99_ms": round(p99 * 1e3, 2), "n": len(done)}

        t_end = stop_at[0]
        baseline = phase_stats(t_start, t_dead or t_end)
        degraded = phase_stats(t_dead or t_end, t_readmit or t_end)
        recovered = phase_stats(t_readmit or t_end, t_end)
        resil = stats["resilience"]
        point = {
            "offered_threads": n_threads,
            "counts": counts,
            "lost": 0,  # every predict() above resolved — join proves it
            "baseline": baseline,
            "degraded": degraded,
            "recovered": recovered,
            "degraded_throughput_ratio":
                round(degraded["rps"] / baseline["rps"], 3)
                if baseline["rps"] else None,
            "quarantine_s":
                round((t_readmit or t_end) - t_dead, 3)
                if t_dead is not None else None,
            "readmitted": t_readmit is not None,
            "resilience_counters": {
                k: v for k, v in sorted(resil.items())
                if isinstance(v, (int, float)) and v},
        }
        total = sum(counts.values())
        point["availability"] = (
            round(counts["ok"] / total, 4) if total else None)
        # end-of-run registry snapshot (telemetry round 2): set-level
        # resilience counters + aggregate serving view, as a /metrics
        # scrape would have seen them
        point["telemetry"] = rs.registry.scalars()
        point["aggregate"] = stats["aggregate"]
        if errs:
            point["errors"] = errs[:3]
        out["sweep"].append(point)
    avails = [p["availability"] for p in out["sweep"]
              if p["availability"] is not None]
    out["value"] = min(avails) if avails else None
    out["wrong_answers"] = sum(p["counts"]["wrong"]
                               for p in out["sweep"])
    out["all_points_readmitted"] = all(p["readmitted"]
                                       for p in out["sweep"])
    return out


def checkpoint_bench(smoke: bool = False):
    """Async-checkpointing overhead entry (the bigdl_tpu.checkpoint
    rider): the SAME training run with checkpointing async (default),
    synchronous (``checkpoint_async=False``), and disabled, reporting
    ``checkpoint_stall_fraction`` — cumulative driver-side checkpoint
    time (device→host capture + bounded enqueue) over run wall time,
    straight from the ``checkpoint/stall_fraction`` registry gauge.
    The async path must keep that fraction a small slice of the
    synchronous baseline (which pays serialize+CRC+fsync inline on the
    driver); the hard gate lives in ``tests/test_checkpoint.py``, this
    entry records the measured numbers (record-never-abort).
    """
    import tempfile

    import numpy as np

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch

    iters, every = (16, 4) if smoke else (96, 8)
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (64,)).astype(np.float32),
                      np.int32(rng.integers(0, 10)))
               for _ in range(512)]

    def run(mode):
        model = nn.Sequential(
            nn.Linear(64, 512), nn.ReLU(), nn.Linear(512, 512), nn.ReLU(),
            nn.Linear(512, 10), nn.LogSoftMax())
        ds = DataSet.array(samples) >> SampleToMiniBatch(64)
        opt = (optim.LocalOptimizer(model, ds, nn.ClassNLLCriterion())
               .set_optim_method(optim.Adam(1e-3))
               .set_end_when(optim.max_iteration(iters)))
        # snapshots live only for the run — repeated bench invocations
        # must not accumulate orphaned checkpoint data in /tmp
        with tempfile.TemporaryDirectory(prefix="bench_ckpt_") as ckdir:
            if mode != "off":
                opt.set_checkpoint(ckdir, optim.several_iteration(every),
                                   async_save=(mode == "async"))
            t0 = time.perf_counter()
            opt.optimize()
            wall = time.perf_counter() - t0
        reg = opt.metrics.registry
        stall_g = reg.get("checkpoint/stall_fraction")
        save_h = reg.get("checkpoint/save_s")
        drv_h = reg.get("checkpoint/driver_stall_s")
        bytes_c = reg.get("checkpoint/bytes_written")
        committed = reg.get("checkpoint/snapshots_committed")
        return {
            "wall_s": round(wall, 3),
            "checkpoint_stall_fraction":
                round(stall_g.value, 5) if stall_g else 0.0,
            "driver_stall_ms_mean":
                round(drv_h.mean * 1e3, 3) if drv_h else 0.0,
            "save_ms_mean": round(save_h.mean * 1e3, 3) if save_h else 0.0,
            "snapshots": committed.value if committed else 0,
            "bytes_written": bytes_c.value if bytes_c else 0,
            # end-of-run registry snapshot (telemetry round 2)
            "telemetry": reg.scalars(),
        }

    out = {"metric": "checkpoint_stall_fraction", "unit": "fraction",
           "toolchain": _toolchain(),
           "config": f"mlp64x512x512x10/adam/batch64/iters{iters}/"
                     f"every{every}",
           "off": run("off"), "sync": run("sync"), "async": run("async")}
    out["value"] = out["async"]["checkpoint_stall_fraction"]
    out["checkpoint_stall_fraction"] = out["value"]
    out["checkpoint_stall_fraction_sync"] = \
        out["sync"]["checkpoint_stall_fraction"]
    sync_f = out["checkpoint_stall_fraction_sync"]
    out["stall_reduction_vs_sync"] = \
        round(1.0 - out["value"] / sync_f, 4) if sync_f > 0 else None
    return out


def elastic_child():
    """``--elastic-child``: one elastic training run on an 8-device
    virtual CPU mesh — world 4, a seeded ``resize@`` shrink to 2
    mid-run, resume from the boundary snapshot with the ZeRO-1 state
    re-sharded, then a regrow back to 4 (``bigdl_tpu.resilience.
    membership``).  Prints the measured JSON: membership epochs,
    ``resilience/resize_downtime_s`` / ``steps_lost_to_resize``
    straight from the registry, and median per-step time split into
    baseline (world 4) / degraded (world 2) / recovered (world 4)
    segments, each segment dropping its boundary step so the restore +
    recompile gap lands in the downtime number, not the throughput."""
    import tempfile

    import numpy as np
    import jax
    from jax.sharding import Mesh

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.utils.config import configure, reset_config

    smoke = os.environ.get("_BENCH_ELASTIC_SMOKE") == "1"
    iters, shrink_at, regrow_at, every = \
        (18, 6, 12, 2) if smoke else (60, 20, 40, 4)
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (64,)).astype(np.float32),
                      np.int32(rng.integers(0, 10)))
               for _ in range(2048)]

    step_t = {}  # neval -> wall clock at replay (last write wins)

    class _Summary:
        def add_train_step(self, step, loss, lr, throughput):
            step_t[step] = time.perf_counter()

        def add_scalar(self, *a):
            pass

        def trigger_for(self, name):
            return None

    model = nn.Sequential(
        nn.Linear(64, 256), nn.ReLU(), nn.Linear(256, 256), nn.ReLU(),
        nn.Linear(256, 10), nn.LogSoftMax())
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    plan = f"resize@at={shrink_at},to=2;resize@at={regrow_at},to=4"
    configure(fault_plan=plan)
    try:
        # snapshots live only for the run — repeated bench invocations
        # must not accumulate orphaned checkpoint data in /tmp
        with tempfile.TemporaryDirectory(prefix="bench_elastic_") as d:
            opt = (optim.DistriOptimizer(
                model, DataSet.array(samples) >> SampleToMiniBatch(32),
                nn.ClassNLLCriterion(), mesh=mesh)
                .set_optim_method(optim.SGD(learning_rate=0.05))
                .set_seed(0)
                .set_train_summary(_Summary())
                .set_end_when(optim.max_iteration(iters)))
            opt.set_checkpoint(d, optim.several_iteration(every))
            t0 = time.perf_counter()
            opt.optimize()  # zero aborted runs IS the acceptance shape
            wall = time.perf_counter() - t0
    finally:
        reset_config()

    def seg_ms(lo, hi):
        # median inter-step ms over (lo, hi]; the boundary step lo+1
        # is excluded so the restore/recompile gap stays out
        ts = [step_t[s] for s in sorted(step_t) if lo + 1 < s <= hi]
        if len(ts) < 2:
            return None
        deltas = sorted(b - a for a, b in zip(ts, ts[1:]))
        return round(deltas[len(deltas) // 2] * 1e3, 2)

    snap = opt.metrics.registry.snapshot()
    hist = snap["histograms"].get("resilience/resize_downtime_s") or {}
    baseline = seg_ms(0, shrink_at)
    degraded = seg_ms(shrink_at, regrow_at)
    recovered = seg_ms(regrow_at, iters)
    return {
        "config": f"mlp64x256x256x10/sgd/batch32/iters{iters}/"
                  f"shrink4to2@{shrink_at}/regrow@{regrow_at}/"
                  f"ckpt_every{every}",
        "wall_s": round(wall, 3),
        "iterations": int(opt.state["neval"]),
        "membership_epoch": int(snap["gauges"].get(
            "resilience/membership_epoch", 0)),
        "worlds": [e.world for e in opt._membership.history()],
        "resize_downtime_s": {
            k: round(hist[k], 4) for k in ("count", "mean", "max", "sum")
            if k in hist},
        "steps_lost_to_resize": snap["counters"].get(
            "resilience/steps_lost_to_resize", 0),
        "step_ms": {"baseline_world4": baseline,
                    "degraded_world2": degraded,
                    "recovered_world4": recovered},
        "recovered_throughput_ratio":
            round(baseline / recovered, 3)
            if baseline and recovered else None,
        # end-of-run registry snapshot (telemetry round 2)
        "telemetry": opt.metrics.registry.scalars(),
    }


def elastic_bench(smoke: bool = False):
    """Elastic-training entry (``--elastic``, the ISSUE-16 rider): a
    child on an 8-device virtual CPU mesh runs a full shrink/regrow
    cycle (world 4 → 2 → 4 via seeded ``resize@`` clauses) and this
    wrapper records the measured resize downtime, steps lost, and the
    recovered-throughput ratio.  The correctness gates — bitwise
    resume at the replay boundary, ``membership_epoch`` == 3, zero
    aborted runs — live in ``tests/test_membership.py``; this entry
    records the numbers (record-never-abort: a failed child is an
    error string in the capture, never a crash)."""
    out = {"metric": "elastic_resize_downtime_s", "unit": "seconds",
           "toolchain": _toolchain()}
    r = subprocess_run(
        [sys.executable, __file__, "--elastic-child"],
        env=_cpu_mesh_env(_BENCH_ELASTIC_SMOKE="1" if smoke else "0"),
        parse=json.loads)
    if not isinstance(r, dict):
        out["error"] = "elastic child failed"
        out["value"] = None
        return out
    out.update(r)
    out["value"] = (r.get("resize_downtime_s") or {}).get("mean")
    out["zero_aborted_runs"] = r.get("membership_epoch") == 3 \
        and r.get("worlds") == [4, 2, 4]
    return out


if __name__ == "__main__":
    if not any(a.endswith("-child") for a in sys.argv):
        # the CPU-pinned children compile little; the entries that
        # compile for the chip keep their programs
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()
    if "--scaling-child" in sys.argv:
        scaling_child()
    elif "--collective-child" in sys.argv:
        collective_child()
    elif "--elastic-child" in sys.argv:
        print(json.dumps(elastic_child()))
    elif "--serving" in sys.argv:
        print(json.dumps(serving_bench("--smoke" in sys.argv)))
    elif "--checkpoint" in sys.argv:
        print(json.dumps(checkpoint_bench("--smoke" in sys.argv)))
    elif "--resilience" in sys.argv:
        print(json.dumps(resilience_bench("--smoke" in sys.argv)))
    elif "--elastic" in sys.argv:
        print(json.dumps(elastic_bench("--smoke" in sys.argv)))
    else:
        main(sys.argv[1:])
