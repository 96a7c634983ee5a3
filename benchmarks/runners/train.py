"""The ``train`` runner: one training job through the product's own
``Optimizer.optimize()`` — ``LocalOptimizer`` on one chip,
``DistriOptimizer(parameter_sharding=True)`` over the mesh on four — fed
by ``DataSet.array >> SampleToMiniBatch``, ended by a wall-clock trigger.

One ``optimize()`` call holds the warm-up and the window.  The train
summary stamps every replayed step with the host clock; the window opens
at the replay of the last warm-up step and closes at the first block end
after ``--seconds``.  ``train_throughput`` is the records of the steps
after the opening one, over the host clock between the opening and the
closing stamp: every step counted was fetched by the driver's own loss
fetch, so the last one was waited for.

In a traced run the profiler covers the LAST ``trace_seconds`` of the
window, the host-side numbers (telemetry shares, the rate behind the
mfu) are taken over the part before it, and the device numbers over the
traced part.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from benchmarks import hlo_count, lib, reference, trace_reduce


class StepStamps:
    """The train-summary surface of the driver: it calls
    ``add_train_step`` once per replayed iteration."""

    def __init__(self):
        self.losses = []
        self.t = []

    def add_train_step(self, step, loss, lr, throughput):
        self.losses.append(float(loss))
        self.t.append(time.perf_counter())

    def add_scalar(self, *a, **k):
        pass

    def trigger_for(self, name):
        return None


def make_end_trigger(seconds, warmup_steps, align, on_open,
                     trace_after=None, on_trace_start=None,
                     on_trace_stop=None):
    """A wall-clock ``Trigger``: False on a probe (a block is planned
    before its time is known), opens the window at the first block end
    at or after ``warmup_steps``, and fires at the first block end after
    ``seconds``.  ``align`` is the steps per dispatch: firing inside a
    block would leave the block's other steps run and not counted."""
    from bigdl_tpu.optim.trigger import Trigger

    class WallClockEnd(Trigger):
        def __init__(self):
            self.t_open = None
            self.step_open = None
            self.t_trace = None       # host clock once start_trace is back
            self.step_trace = None
            self.t_close = None
            self.done = False

        def __call__(self, state):
            if state.get("probe"):
                return False
            if self.done:
                return True
            n = state["neval"]
            if n % align:
                return False
            now = time.perf_counter()
            if self.t_open is None:
                if n >= warmup_steps:
                    self.t_open, self.step_open = now, n
                    on_open()
                return False
            if (trace_after is not None and self.t_trace is None
                    and now - self.t_open >= trace_after):
                on_trace_start()
                self.t_trace, self.step_trace = time.perf_counter(), n
                return False
            if now - self.t_open >= seconds:
                self.t_close = now
                if self.t_trace is not None:
                    on_trace_stop()
                self.done = True
                return True
            return False

    return WallClockEnd()


def stack_batch(samples, b: int, size: int):
    """Batch ``b`` of the first epoch, which the data set hands out in
    insertion order, as host arrays."""
    chunk = samples[b * size:(b + 1) * size]
    return (np.stack([s.feature for s in chunk]),
            np.stack([s.label for s in chunk]))


def program_facts(ctx, opt, k: int) -> dict:
    """What the compiler says of the optimizer's OWN k-step block,
    lowered once more by the same builders on the trained state
    (chip_smoke.py ``lower_step``'s recipe): memory per device, the
    collectives' wire bytes, and the names of the instructions that are
    collectives or Pallas calls.  None of it depends on the seed, so a
    checkout computes it once and keeps it beside its compile cache."""
    path = os.path.join(ctx.out_root, "facts",
                        f"{ctx.workload}{'-tiny' if ctx.tiny else ''}.json")
    if os.path.isfile(path):
        with open(path) as f:
            return json.load(f)
    import jax
    import jax.numpy as jnp
    tmap = jax.tree_util.tree_map
    fn = opt._build_block_fn(opt._loss_and_grad_fn(), k)
    it = iter(opt.dataset.data(train=True))
    mbs = [next(it) for _ in range(k)]
    xs = tmap(lambda *a: np.stack([np.asarray(v) for v in a]),
              *[mb.input for mb in mbs])
    ys = np.stack([np.asarray(mb.target) for mb in mbs])
    xs, ys = opt._place_train_block(xs, ys)
    rngs = jnp.stack([jax.random.PRNGKey(0)] * k)
    compiled = fn.lower(opt.model._params, opt.model._state,
                        opt._final_opt_state, xs, ys,
                        jnp.zeros((k,), jnp.float32),
                        jnp.zeros((k,), jnp.int32), rngs).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    facts = {
        "k": k,
        "memory": {
            "temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        "program_bytes": int(mem.temp_size_in_bytes
                             + mem.argument_size_in_bytes
                             + mem.output_size_in_bytes
                             - mem.alias_size_in_bytes),
        "wire_bytes": hlo_count.collective_wire_bytes(text),
        "op_names": hlo_count.op_names(text),
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".part", "w") as f:
        json.dump(facts, f)
    os.replace(path + ".part", path)
    return facts


def phase_seconds(events, t_a: float, t_b: float, by="cat") -> dict:
    """Seconds per span category (or, ``by="name"``, per span name) of
    the product's telemetry between two host-clock times (its tracer
    stamps with ``perf_counter`` too).  The virtual "device" track is
    left out: it is not host time."""
    totals: dict = {}
    for ph, name, cat, t0_ns, dur_ns, tid, _args, _flow in events:
        if ph != "X" or tid == "device" or cat == "pipeline":
            continue
        key = (cat or "uncategorized") if by == "cat" else name
        if t_a <= t0_ns / 1e9 < t_b:
            totals[key] = totals.get(key, 0.0) + dur_ns / 1e9
    return totals


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.dataset as dataset
    from bigdl_tpu import optim
    from bigdl_tpu.engine import Engine

    cfg, traffic, builder = ctx.config, ctx.traffic, ctx.builder
    tr = cfg["train"]
    chips = ctx.chips
    batch = tr["batch_per_chip"] * chips
    k = int(tr["steps_per_dispatch"])
    warmup_steps = int(traffic["warmup_blocks"]) * k
    n_check = int(traffic["check_losses"])
    compute_dtype = {None: None, "bfloat16": jnp.bfloat16}[
        tr["compute_dtype"]]
    sgd = dict(tr["optimizer"])

    if jax.device_count() != chips:
        # the rehearsal on a wider virtual mesh; on the chip the default
        # Engine.get_mesh(), every device, is the cell
        from jax.sharding import Mesh
        Engine.set_mesh(Mesh(np.array(jax.devices()[:chips]), ("data",)))

    # ---- data and weights from the seed
    t = time.perf_counter()
    epoch_steps = int(tr["epoch_records"]) // batch
    samples = builder.make_samples(cfg, ctx.seed, batch, epoch_steps)
    t_data = time.perf_counter() - t
    model = builder.build_model(cfg)
    model._params, model._state = jax.jit(model.init)(
        jax.random.PRNGKey(ctx.seed))
    crit = builder.criterion(cfg)
    jax.block_until_ready(model._params)
    ctx.note(f"data ({len(samples)} samples, epoch of "
             f"{epoch_steps} steps of {batch}): {t_data:.1f} s; "
             f"weights: {time.perf_counter() - t - t_data:.1f} s")

    # ---- the plain reference's first losses (set-up, outside the window)
    t = time.perf_counter()
    ref = reference.reference_losses(
        builder.reference_model(cfg, model), crit, model._params,
        model._state,
        [stack_batch(samples, b, batch) for b in range(n_check)],
        shards=chips, compute_dtype=compute_dtype, sgd=sgd)
    ctx.note(f"plain reference, {n_check} steps: "
             f"{time.perf_counter() - t:.1f} s; losses {ref}")

    # ---- the job
    # the batch assembler the configuration names, as the product's
    # shipped example for this model attaches it
    assembler = getattr(dataset, tr["assembler"])
    ds = dataset.DataSet.array(samples) >> assembler(batch)
    if chips == 1:
        opt = optim.LocalOptimizer(model, ds, crit)
    else:
        opt = optim.DistriOptimizer(model, ds, crit,
                                    parameter_sharding=True)
    opt.set_optim_method(optim.SGD(learning_rate=sgd["lr"],
                                   momentum=sgd["momentum"],
                                   weight_decay=sgd["weight_decay"]))
    if compute_dtype is not None:
        opt.set_compute_dtype(compute_dtype)
    opt.set_steps_per_dispatch(k)
    stamps = StepStamps()
    trace_dir = os.path.join(ctx.out_dir, "xplane")
    trace_s = min(float(traffic["trace_seconds"]), ctx.seconds / 2.0)
    marks = {}

    def trace_start():
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    end = make_end_trigger(
        ctx.seconds, warmup_steps, k,
        on_open=lambda: marks.update(open=ctx.clock.mark()),
        trace_after=ctx.seconds - trace_s if ctx.trace else None,
        on_trace_start=trace_start,
        on_trace_stop=jax.profiler.stop_trace)
    opt.set_train_summary(stamps).set_end_when(end).set_seed(ctx.seed)
    if ctx.trace:
        opt.set_telemetry(True)

    opt.optimize()
    if end.t_open is None or end.t_close is None:
        raise lib.BenchFailure("the window never opened or never closed")
    compiles = ctx.clock.since(marks["open"])

    # ---- the window, from the stamps
    i_open = end.step_open - 1            # index of the opening step
    t_open = stamps.t[i_open]
    losses_win = stamps.losses[i_open + 1:]
    t_last = stamps.t[-1]
    steps = len(losses_win)
    if steps < 1 or t_last <= t_open:
        raise lib.BenchFailure(f"{steps} steps in the window")
    throughput = lib.rate(steps * batch, t_last - t_open)
    setup_s = t_open - ctx.t0
    ctx.note(f"window: {steps} steps of {batch} records in "
             f"{t_last - t_open:.3f} s; set-up {setup_s:.1f} s; "
             f"{len(stamps.losses)} steps in all")
    blocks = np.diff(stamps.t[i_open::k])
    order = np.argsort(blocks)[::-1][:3]
    ctx.note(f"seconds per block of {k}: min {blocks.min():.4f} median "
             f"{np.median(blocks):.4f} max {blocks.max():.4f}; longest at "
             f"blocks {[(int(i), round(float(blocks[i]), 4)) for i in order]}")
    with open(os.path.join(ctx.out_dir, f"stamps.seed{ctx.seed}."
                           f"trace{int(ctx.trace)}.json"), "w") as f:
        json.dump({"t0": ctx.t0, "step_open": end.step_open, "k": k,
                   "t": stamps.t, "losses": stamps.losses}, f)

    # ---- correct?
    got = stamps.losses[:n_check]
    diff = reference.loss_diff(got, ref)
    bad = [v for v in losses_win if not np.isfinite(v)]
    ctx.note(f"first losses {got} vs plain reference {ref}: max relative "
             f"difference {diff:.3e} (tolerance {tr['loss_rtol']})")
    ctx.note(f"compiles inside the window: {compiles}")
    correct = (diff <= tr["loss_rtol"] and not bad
               and compiles["backend_compiles"] == 0
               and compiles["cache_misses"] == 0)
    if ctx.on_tpu:
        plats = {d.platform for leaf in
                 jax.tree_util.tree_leaves(opt.model._params)
                 for d in leaf.devices()}
        correct = correct and plats == {"tpu"}

    facts = program_facts(ctx, opt, k)
    ctx.note(f"optimizer's own {k}-step block, per device: "
             f"{facts['memory']}; {facts['tpu_custom_calls']} "
             f"tpu_custom_call; wire bytes {facts['wire_bytes']}")
    result = {
        "correct": bool(correct),
        "attempted": steps,
        "failed": len(bad),
        "end_to_end": {"train_throughput": throughput,
                       "setup_s": setup_s},
        "program_bytes": facts["program_bytes"],
        "observed": None,
    }
    if not ctx.trace:
        return result

    # ---- the traced run's observations, for the per-layer readers
    t_host_end = end.t_trace
    host_steps = end.step_trace - end.step_open
    host_rate = lib.rate(host_steps * batch, t_host_end - t_open)
    tel = opt._telemetry
    events = tel.tracer.events()
    phases = phase_seconds(events, t_open, t_host_end)
    ctx.note(f"host part of the window: {host_steps} steps in "
             f"{t_host_end - t_open:.3f} s; telemetry phase seconds "
             f"{phases}; by span name "
             f"{phase_seconds(events, t_open, t_host_end, 'name')}"
             f"; dropped telemetry events {tel.tracer.dropped_events}")
    t = time.perf_counter()
    xplane = trace_reduce.find_xplane(trace_dir)
    red = trace_reduce.reduce_file(xplane, op_names=facts["op_names"])
    ctx.note(f"xplane {os.path.getsize(xplane)} bytes reduced in "
             f"{time.perf_counter() - t:.1f} s")
    dev0 = red["devices"][0] if red["devices"] else {}
    # the program that took most device time is the optimizer's block
    main = max(dev0.get("modules", {}).values(),
               key=lambda m: m["seconds"], default=None)
    trace_steps = main["whole_executions"] * k if main else 0
    for d in red["devices"]:
        ctx.note(f"{d['plane']}: busy {d['busy_s']:.4f} s of "
                 f"{d['window_s']:.4f} s, {d['events']} op events, "
                 f"collectives {d.get('collective_s', 0):.4f} s, pallas "
                 f"{d.get('pallas_s', 0):.4f} s; modules "
                 f"{d.get('modules')}")
    top_phase = max(phases.items(), key=lambda kv: kv[1],
                    default=("none", 0.0))[0]
    result["observed"] = {
        "chips": chips,
        "host_window_s": t_host_end - t_open,
        "host_records_per_s": host_rate,
        "phase_seconds": phases,
        "flops_per_record": builder.train_flops_per_record(cfg),
        "peaks": lib.peaks_for(jax.devices()[0].device_kind)
        if ctx.on_tpu else None,
        "facts": facts,
        "trace_device0": dev0,
        "trace_steps": trace_steps,
    }
    result["device_busy"] = {"busy_s": red["busy_s"],
                             "window_s": red["window_s"]}
    result["breakdown"] = {
        "device_ops": [[n, s] for n, s in
                       trace_reduce.top(dev0.get("op_self_s", {}), 10,
                                        dev0.get("full_names"))],
        "idle_gaps": [[f"gap_at_{at:.3f}s.window_top_host_phase."
                       f"{top_phase}", length]
                      for length, at in dev0.get("idle_gaps", [])[:5]],
    }
    if not ctx.keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result
