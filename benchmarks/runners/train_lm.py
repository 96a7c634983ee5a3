"""The ``train_lm`` runner: one language-model training job through
``LocalOptimizer.optimize()`` over ``DataSet.array >> SampleToMiniBatch``,
for a model whose parameters fill most of the chip.

It differs from the ``train`` runner (whose window, stamps, trigger and
span arithmetic it imports and does not restate) in what memory forces:

- **the plain reference runs FIRST**, on parameters of its own drawn
  from the key the optimizer will draw its own from
  (``jax.random.split(PRNGKey(seed))[1]``, eagerly, leaf by leaf, as
  ``_optimize_impl`` does), ``check_losses`` in-place SGD steps through
  one jitted, donating step; its buffers are deleted before the
  optimizer exists.  ``train`` + ``benchmarks/reference.py`` hold four
  f32 copies of the parameters at once, 19.5 GB here;
- **the model goes to the optimizer UN-initialised**: ``_optimize_impl``
  copies a caller's ``model._params`` before it donates, a second
  4.9 GB; un-initialised it draws the weights once, from ``set_seed``;
- the configuration's builder hands over the reference's step
  (``reference_step``) and, where its model carries counters as state,
  reads them (``counters``) and judges them (``counters_correct``): the
  runner passes them to the readers and knows no model's state layout;
  the observations also carry the compiled block's table of named
  device scopes (``benchmarks/hlo_scopes.py``).

**What is compared.**  At initialisation the loss of a language model
is ``ln(vocabulary)`` whatever its layers compute, so the losses alone
would pass a model that computes nothing.  The comparison that decides
is of the PARAMETERS after ``check_losses`` steps at the
configuration's ``check_lr`` (the job's own rate may move a weight by
less than an ulp a step, and the comparison would read the update's
rounding: the configuration says), LEAF BY LEAF: a first, short
``optimize()`` (same seed, same records, ended by ``max_iteration``;
the block takes the rate as an argument, so it is the measured job's
program) hands back the product's parameters, and a leaf's
``param_change_error`` is ``|p_product - p_reference| / |p_reference -
p_initial|`` over a fixed strided sample of the leaf (both start from
the same bits, so the numerator is the difference of the two updates).
A leaf left unchanged reads 1 whatever the others do; a leaf the
reference left unchanged must be unchanged in the product too (0, else
infinite).  Leaves of one kind (the path without the layer's number:
``mixer.A_log``) share a limit, ``param_change_tol[kind]`` or its
``"*"``; the worst leaf of each kind is held to it.  One global norm
would hide a small leaf behind the expert weights: it is printed and
decides nothing.  The measured job is a second ``optimize()`` on the
model un-initialised again; its first loss (the same weights drawn,
the same record, no update yet) must repeat the first job's to the
last bit.

``correct``: every kind's worst leaf within its limit, the first
``check_losses`` losses within ``loss_rtol`` of the reference's and
the first repeated bit for bit by the measured job, every loss of the window
finite, no compile and no cache miss inside the window, the trained
parameters on the TPU, no kernel of the block under no named scope, and
the builder's verdict on the counters (no assignment dropped).  One
chip, K steps a dispatch as the configuration says.

``setup_s`` is what a user of the system pays before the first counted
step: process start to the opening step's replay LESS the seconds of
the comparison (the reference's weights and steps, the short job),
which are the benchmark's own and are said in the log beside it."""

from __future__ import annotations

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import hlo_count, hlo_scopes, lib, trace_reduce

train = lib.load_module("runners", "train")


def block_facts(ctx, opt, k: int, record_shapes) -> dict:
    """``train.program_facts`` for a block that is handed SHAPES (the
    trained parameters stay where they are), plus the table of named
    device scopes.  Nothing in it depends on the seed: a checkout
    computes it once."""
    path = os.path.join(ctx.out_root, "facts",
                        f"{ctx.workload}{'-tiny' if ctx.tiny else ''}"
                        ".lm.json")
    if os.path.isfile(path):
        with open(path) as f:
            return json.load(f)
    spec = jax.ShapeDtypeStruct
    fn = opt._build_block_fn(opt._loss_and_grad_fn(), k)
    carried = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        (opt.model._params, opt.model._state, opt._final_opt_state))
    xs, ys = (spec((k,) + shape, dtype) for shape, dtype in record_shapes)
    compiled = fn.lower(*carried, xs, ys, spec((k,), jnp.float32),
                        spec((k,), jnp.int32),
                        spec((k, 2), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    scopes = hlo_scopes.instruction_scopes(
        text, getattr(ctx.builder, "COMPILER_OPS", None))
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
        "scopes": scopes,
        "unscoped_kernels": hlo_scopes.unscoped_kernels(text, scopes),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".part", "w") as f:
        json.dump(facts, f)
    os.replace(path + ".part", path)
    return facts


SAMPLE_PER_LEAF = 65536


def sample_tree(tree) -> dict:
    """``{leaf's path: a fixed strided sample of it}`` on the host: about
    ``SAMPLE_PER_LEAF`` elements of a leaf, the whole of a small one."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat = leaf.reshape(-1)
        out[".".join(str(getattr(k, "key", k)) for k in path)] = np.asarray(
            flat[::max(1, flat.size // SAMPLE_PER_LEAF)], np.float32)
    return out


def leaf_kind(path: str) -> str:
    """A leaf's path without the numbers in it: the layers' leaves of
    one name are one kind (``layers.3.mixer.A_log`` -> ``mixer.A_log``)."""
    parts = [p for p in path.split(".") if not p.isdigit()]
    return ".".join(parts[1:] if len(parts) > 1 else parts)


def param_change_errors(initial: dict, reference: dict, got: dict) -> dict:
    """Per leaf, ``|got - reference| / |reference - initial|`` over the
    samples.  A leaf the reference left unchanged: 0 if ``got`` left it
    unchanged too, else infinite."""
    errors = {}
    for path, ref in reference.items():
        change = float(np.linalg.norm(ref - initial[path]))
        off = float(np.linalg.norm(got[path] - ref))
        errors[path] = off / change if change else (
            0.0 if off == 0.0 else float("inf"))
    return errors


def worst_by_kind(errors: dict) -> dict:
    """``{kind: (its worst leaf's error, that leaf's path)}``."""
    worst: dict = {}
    for path, err in errors.items():
        kind = leaf_kind(path)
        if kind not in worst or not err <= worst[kind][0]:
            worst[kind] = (err, path)
    return worst


def params_correct(errors: dict, tol: dict):
    """Every kind's worst leaf against its limit (``tol[kind]``, or
    ``tol["*"]``).  Returns ``(ok, lines)``: one line a kind, the one
    nearest to (or farthest over) its limit first."""
    rows = []
    for kind, (err, path) in worst_by_kind(errors).items():
        limit = tol.get(kind, tol["*"])
        share = err / limit if np.isfinite(err) else float("inf")
        rows.append((share, f"{kind}: {err:.4e} at {path} (limit {limit})"))
    rows.sort(reverse=True)
    return rows[0][0] <= 1.0, [line for _, line in rows]


def global_error(initial: dict, reference: dict, got: dict) -> float:
    """The ONE norm over every sample: printed, decides nothing."""
    cat = lambda d: np.concatenate([d[path] for path in reference])
    ref = cat(reference)
    return float(np.linalg.norm(cat(got) - ref)
                 / np.linalg.norm(ref - cat(initial)))


def delete_tree(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.delete()


def reference_run(seed: int, model, step, samples, batch: int,
                  n_check: int, lr: float):
    """The plain reference: parameters of its own from the optimizer's
    init key, drawn as ``_optimize_impl`` draws its own (eagerly: the
    same programs, so the same bits), ``n_check`` in-place SGD steps at
    ``lr`` through ``step`` (the builder's ``reference_step``).  Returns the
    losses, the samples of the parameters before and after, and the
    seconds the draw took; nothing is left on the device."""
    t = time.perf_counter()
    init_key = jax.random.split(jax.random.PRNGKey(seed))[1]
    params, _ = model.init(init_key)
    initial = sample_tree(params)
    draw_s = time.perf_counter() - t
    losses = []
    for b in range(n_check):
        x, y = train.stack_batch(samples, b, batch)
        loss, params = step(params, x, y, np.float32(lr))
        losses.append(float(loss))
    final = sample_tree(params)
    delete_tree(params)
    return losses, initial, final, draw_s


def scope_report(dev0: dict, scopes: dict, steps: float, top: int = 3):
    """Milliseconds a step under each named scope with its largest
    instructions, and the largest instructions under no scope."""
    selfs = dev0.get("op_self_s") or {}
    by: dict = {}
    for name, seconds in selfs.items():
        by.setdefault(scopes.get(name, "(no scope)"), []).append(
            (seconds, name))
    lines = []
    for scope, ops in sorted(by.items(), key=lambda kv: -sum(
            s for s, _ in kv[1])):
        ops.sort(reverse=True)
        n_top = 12 if scope == "(no scope)" else top
        lines.append(
            f"{scope}: {1e3 * sum(s for s, _ in ops) / steps:.2f} ms a "
            f"step in {len(ops)} instructions; largest "
            + ", ".join(f"{n} {1e3 * s / steps:.2f}"
                        for s, n in ops[:n_top]))
    return lines


def job(cfg, builder, model, samples, seed: int, summary, end_when,
        lr=None):
    """The cell's training job on ``model`` as it stands (un-initialised:
    ``_optimize_impl`` then draws the weights from ``seed``); ``lr``:
    another learning rate than the configuration's (the block takes it
    as an argument: the same program)."""
    import bigdl_tpu.dataset as dataset
    from bigdl_tpu import optim
    tr = cfg["train"]
    sgd = tr["optimizer"]
    ds = dataset.DataSet.array(samples) >> getattr(
        dataset, tr["assembler"])(int(tr["batch_per_chip"]))
    opt = optim.LocalOptimizer(model, ds, builder.criterion(cfg))
    opt.set_optim_method(optim.SGD(learning_rate=lr or sgd["lr"],
                                   momentum=sgd["momentum"],
                                   weight_decay=sgd["weight_decay"]))
    if tr["compute_dtype"] is not None:
        opt.set_compute_dtype(jnp.dtype(tr["compute_dtype"]))
    opt.set_steps_per_dispatch(int(tr["steps_per_dispatch"]))
    opt.set_train_summary(summary).set_end_when(end_when)
    return opt.set_seed(seed)


def product_run(cfg, builder, model, samples, seed: int, n_check: int,
                lr: float):
    """The product's first ``n_check`` steps at ``lr`` as a short job of
    its own.
    Returns the losses and the sample of its parameters; the model is
    left un-initialised again and nothing on the device."""
    from bigdl_tpu import optim
    first = train.StepStamps()
    job(cfg, builder, model, samples, seed, first,
        optim.max_iteration(n_check), lr).optimize()
    got = sample_tree(model._params)
    delete_tree((model._params, model._state))
    model._params = model._state = None
    return first.losses, got


def run(ctx) -> dict:
    cfg, traffic, builder = ctx.config, ctx.traffic, ctx.builder
    tr = cfg["train"]
    if ctx.chips != 1:
        raise lib.BenchFailure("train_lm drives LocalOptimizer: one chip")
    batch = int(tr["batch_per_chip"])
    k = int(tr["steps_per_dispatch"])
    warmup_steps = int(traffic["warmup_blocks"]) * k
    n_check = int(traffic["check_losses"])
    compute_dtype = {None: None, "bfloat16": jnp.bfloat16}[
        tr["compute_dtype"]]

    # ---- data from the seed; the model stays un-initialised
    t = time.perf_counter()
    epoch_steps = int(tr["epoch_records"]) // batch
    samples = builder.make_samples(cfg, ctx.seed, batch, epoch_steps)
    model = builder.build_model(cfg)
    ctx.note(f"data ({len(samples)} records of {tr['seq_len']} tokens, "
             f"epoch of {epoch_steps} steps of {batch}): "
             f"{time.perf_counter() - t:.1f} s")

    # ---- the comparison, before the measured job holds anything: the
    # plain reference's first steps, then the product's as a short job
    # whose parameters are compared and dropped.  The benchmark's own
    # seconds: stamped, and taken out of set-up
    t_check = time.perf_counter()
    check_lr = tr.get("check_lr", tr["optimizer"]["lr"])
    ref, p_initial, p_reference, draw_s = reference_run(
        ctx.seed, model, builder.reference_step(cfg, compute_dtype),
        samples, batch, n_check, check_lr)
    reference_s = time.perf_counter() - t_check
    ctx.note(f"plain reference, weights ({draw_s:.1f} s) + {n_check} "
             f"steps at lr {check_lr}: {reference_s:.1f} s; losses {ref}")
    got, p_product = product_run(cfg, builder, model, samples, ctx.seed,
                                 n_check, check_lr)
    check_s = time.perf_counter() - t_check
    errors = param_change_errors(p_initial, p_reference, p_product)
    params_ok, said = params_correct(errors, tr["param_change_tol"])
    ctx.note(f"product's first {n_check} steps: "
             f"{check_s - reference_s:.1f} s; losses {got}; parameters' "
             f"change after them, |product - reference| / |reference - "
             f"initial| by leaf, the worst leaf of each kind (1 = the "
             f"leaf learned nothing): within the limits: {params_ok}; "
             f"one norm over all {len(errors)} leaves' samples (decides "
             f"nothing): "
             f"{global_error(p_initial, p_reference, p_product):.4e}")
    for line in said:
        ctx.note("  " + line)
    with open(os.path.join(ctx.out_dir, f"param_change.seed{ctx.seed}."
                           f"trace{int(ctx.trace)}.json"), "w") as f:
        json.dump(errors, f, indent=0)

    # ---- the job
    stamps = train.StepStamps()
    trace_dir = os.path.join(ctx.out_dir, "xplane")
    trace_s = min(float(traffic["trace_seconds"]), ctx.seconds / 2.0)
    marks = {}

    def trace_start():
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    end = train.make_end_trigger(
        ctx.seconds, warmup_steps, k,
        on_open=lambda: marks.update(open=ctx.clock.mark()),
        trace_after=ctx.seconds - trace_s if ctx.trace else None,
        on_trace_start=trace_start,
        on_trace_stop=jax.profiler.stop_trace)
    opt = job(cfg, builder, model, samples, ctx.seed, stamps, end)
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
    setup_s = t_open - ctx.t0 - check_s
    ctx.note(f"window: {steps} steps of {batch} records in "
             f"{t_last - t_open:.3f} s; set-up {setup_s:.1f} s, and "
             f"{check_s:.1f} s of comparison before it that it leaves "
             f"out; {len(stamps.losses)} steps in all")
    blocks = np.diff(stamps.t[i_open::k])
    ctx.note(f"seconds per block of {k}: min {blocks.min():.4f} median "
             f"{np.median(blocks):.4f} max {blocks.max():.4f}")
    with open(os.path.join(ctx.out_dir, f"stamps.seed{ctx.seed}."
                           f"trace{int(ctx.trace)}.json"), "w") as f:
        json.dump({"t0": ctx.t0, "step_open": end.step_open, "k": k,
                   "t": stamps.t, "losses": stamps.losses}, f)

    # ---- correct?
    diff = float(np.max(np.abs(np.subtract(got[:n_check], ref))
                        / np.abs(ref)))
    repeated = stamps.losses[0] == got[0]   # before any update, any rate
    bad = [v for v in losses_win if not np.isfinite(v)]
    ctx.note(f"first losses {got} vs plain reference {ref}: max relative "
             f"difference {diff:.3e} (tolerance {tr['loss_rtol']}); the "
             f"measured job repeats the first: {repeated}; last loss "
             f"{stamps.losses[-1]}")
    ctx.note(f"compiles inside the window: {compiles}")
    correct = (diff <= tr["loss_rtol"] and repeated and params_ok
               and not bad and compiles["backend_compiles"] == 0
               and compiles["cache_misses"] == 0)
    # the model's own counters, where the builder reads any: a dict of
    # observations (name -> what that name's readers take)
    counters = {}
    if hasattr(builder, "counters"):
        counters = builder.counters(
            cfg, model, opt.model._state, batch * int(tr["seq_len"]),
            len(stamps.losses))
        ctx.note(f"the model's counters over {len(stamps.losses)} steps: "
                 f"{counters}")
        correct = correct and builder.counters_correct(counters)
    if ctx.on_tpu:
        plats = {d.platform for leaf in
                 jax.tree_util.tree_leaves(opt.model._params)
                 for d in leaf.devices()}
        correct = correct and plats == {"tpu"}

    record = samples[0]
    facts = block_facts(ctx, opt, k, [
        ((batch,) + np.shape(record.feature), np.asarray(record.feature).dtype),
        ((batch,) + np.shape(record.label), np.asarray(record.label).dtype)])
    ctx.note(f"optimizer's own {k}-step block, per device: "
             f"{facts['memory']}; {facts['tpu_custom_calls']} "
             f"tpu_custom_call; {len(facts['scopes'])} instructions under "
             f"a named device scope; kernels under none (their time "
             f"would be read as nobody's): {facts['unscoped_kernels']}")
    result = {
        "correct": bool(correct and not facts["unscoped_kernels"]),
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
    phases = train.phase_seconds(events, t_open, t_host_end)
    ctx.note(f"host part of the window: {host_steps} steps in "
             f"{t_host_end - t_open:.3f} s; telemetry phase seconds "
             f"{phases}; dropped telemetry events "
             f"{tel.tracer.dropped_events}")
    xplane = trace_reduce.find_xplane(trace_dir)
    red = trace_reduce.reduce_file(xplane, op_names=facts["op_names"])
    dev0 = red["devices"][0] if red["devices"] else {}
    # the program that took most device time is the optimizer's block
    main = max(dev0.get("modules", {}).values(),
               key=lambda m: m["seconds"], default=None)
    trace_steps = main["whole_executions"] * k if main else 0
    for d in red["devices"]:
        ctx.note(f"{d['plane']}: busy {d['busy_s']:.4f} s of "
                 f"{d['window_s']:.4f} s, {d['events']} op events; "
                 f"modules {d.get('modules')}")
    if trace_steps:
        for line in scope_report(dev0, facts["scopes"], trace_steps):
            ctx.note(line)
    top_phase = max(phases.items(), key=lambda kv: kv[1],
                    default=("none", 0.0))[0]
    result["observed"] = {
        "chips": 1,
        "host_window_s": t_host_end - t_open,
        "host_records_per_s": host_rate,
        "phase_seconds": phases,
        "flops_per_record": builder.train_flops_per_record(cfg),
        "peaks": lib.peaks_for(jax.devices()[0].device_kind)
        if ctx.on_tpu else None,
        "facts": facts,
        "scopes": facts["scopes"],
        **counters,
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
