"""The ``decode`` runner: generation requests into ONE
``bigdl_tpu.serving.decode.DecodeService``, its public entry point, in
process (the HTTP front end is bypassed), on an open-loop schedule drawn
by ``benchmarks/open_loop.py`` from the traffic file.

Set-up: the benchmark's own weights on the device (the builder), the
service built over them (it compiles its step, a prefill and a splice a
bucket), one short request through every bucket, then the schedule
starts and runs ``warmup_s`` before the window opens.  The window is
``--seconds`` of host clock.  Every token is stamped by the service's
own ``on_token`` callback, on the scheduler's thread:

- ``decode_throughput``: tokens stamped inside the window, over it;
- ``itl_p99_ms``: the 99th percentile (nearest rank) of the gaps between
  consecutive tokens of one answer, both stamps inside, over all
  answers; the median is printed beside it and is a per-layer metric of
  a traced run.

When the window closes the service is stopped without draining (above
the knee a backlog is the point), the peak memory is read, the service
and its strips are freed, and the plain reference runs over a sample of
the answers COMPLETED in the window: drawn from the seed, the longest
prompt + answer always in it.  What is compared is the gap by which a
served token's log-probability lies below the reference's best at its
position: its mean over the served tokens (``serve.mean_gap_limit`` of
the configuration: the number that tells a lower precision) and the
widest (``serve.gap_limit``: a token that is plainly wrong).

In a traced run the profiler covers the LAST ``trace_seconds`` of the
window; host-clock numbers (the mfu) are taken over the part before it.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import threading
import time

import numpy as np

from benchmarks import lib, open_loop, trace_programs, trace_reduce

# the service's executables by the names its module gives their
# functions (serving/decode.py): what the device trace calls them
PROGRAM_KINDS = {"step": ["_step_fn"], "prefill": ["_prefill_fn"],
                 "splice": ["_splice_fn"]}
COUNTERS = ("decode/steps", "decode/active_slot_steps",
            "decode/admissions", "decode/tokens_generated")


def build_service(ctx, weights):
    """ONE DecodeService over the benchmark's weights, as the
    configuration's ``serve`` group and the traffic file say."""
    from bigdl_tpu.serving.decode import DecodeService
    cfg, sv = ctx.config, ctx.config["serve"]
    model = ctx.builder.build_model(cfg)
    params = ctx.builder.product_params(cfg, model, weights)
    return DecodeService(
        model, params=params, state={}, slots=sv["slots"],
        max_seq_len=sv["max_seq_len"], max_prompt_len=sv["max_prompt_len"],
        prefill_buckets=sv["prefill_buckets"], eos_id=sv["eos_id"],
        queue_capacity=int(ctx.traffic["queue_capacity"]))


def warm_buckets(svc, vocab: int, seed: int) -> None:
    """One request through every prefill bucket and a few steps, so that
    no executable runs for the first time inside the window."""
    rng = np.random.default_rng(seed)
    futs = [svc.submit(rng.integers(0, vocab, b).astype(np.int32),
                       max_new_tokens=3) for b in svc.buckets]
    for f in futs:
        f.result(timeout=300)


def start_trace(jax, path: str) -> None:
    """The profiler as the other runners start it: device planes and
    the host's TraceMe events, no Python tracer."""
    shutil.rmtree(path, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(path, profiler_options=options)


def read_counters(svc) -> dict:
    reg = svc.metrics.registry
    return {name: reg.counter(name).value for name in COUNTERS}


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def drive(svc, sched: dict, warmup_s: float, seconds: float,
          at_open=None, trace_s: float = 0.0, trace_start=None,
          trace_stop=None) -> dict:
    """Send the schedule, whether or not earlier requests have finished,
    and stamp every token.  Returns the stamps and the futures; the
    service is left running."""
    n = sched["n"]
    stamps = [[] for _ in range(n)]
    sent_at = [None] * n
    futures = [None] * n
    refused = []
    t_start = time.perf_counter() + 0.05
    t_open = t_start + warmup_s
    t_close = t_open + seconds

    def stamper(i):
        row = stamps[i]
        return lambda _index, _token: row.append(time.perf_counter())

    def generate():
        for i in range(n):
            due = t_start + float(sched["due_s"][i])
            if due >= t_close:
                return
            _sleep_until(due)
            sent_at[i] = time.perf_counter()
            try:
                futures[i] = svc.submit(
                    sched["prompts"][i],
                    max_new_tokens=int(sched["output_len"][i]),
                    on_token=stamper(i))
            except Exception as e:  # refused: a failure, never a fast one
                refused.append((i, repr(e)))

    gen = threading.Thread(target=generate, name="decode-bench-generator")
    gen.start()
    marks = {}
    try:
        _sleep_until(t_open)
        marks["open"] = read_counters(svc)
        if at_open:
            at_open()
        t_trace = None
        if trace_s > 0:
            _sleep_until(t_close - trace_s)
            trace_start()
            t_trace = time.perf_counter()
            marks["trace"] = read_counters(svc)
        _sleep_until(t_close)
        marks["close"] = read_counters(svc)
        if trace_s > 0:
            trace_stop()
    finally:
        gen.join()
    return {"t_start": t_start, "t_open": t_open, "t_close": t_close,
            "t_trace": t_trace, "stamps": stamps, "sent_at": sent_at,
            "futures": futures, "refused": refused, "marks": marks}


def settle(run: dict, sched: dict) -> dict:
    """After the service has stopped: what became of every request."""
    done, failed, unfinished = [], list(run["refused"]), []
    for i, fut in enumerate(run["futures"]):
        if fut is None:
            continue
        if not fut.done():
            unfinished.append(i)
            continue
        exc = fut.exception()
        if exc is None:
            done.append(i)
        elif type(exc).__name__ == "ServiceClosed":
            unfinished.append(i)      # cut by the runner's own stop
        else:
            failed.append((i, repr(exc)))
    late = [run["sent_at"][i] - (run["t_start"] + float(sched["due_s"][i]))
            for i in range(sched["n"]) if run["sent_at"][i] is not None]
    return {"done": done, "failed": failed, "unfinished": unfinished,
            "late_s": late}


def sample_rows(sched, results, completed, k: int, seed: int, width: int):
    """``k`` of the answers completed in the window, drawn from the
    seed, the longest prompt + answer always among them, as rows of
    prompt + answer padded on the right to ``width``; with, per row,
    where the answer starts and ends."""
    if not completed:
        return [], np.zeros((0, width), np.int32), []
    total = {i: int(sched["prompt_len"][i]) + len(results[i].tokens)
             for i in completed}
    longest = max(completed, key=lambda i: (total[i], -i))
    rest = [i for i in completed if i != longest]
    rng = np.random.default_rng(seed + 1)
    picked = [longest] + [rest[j] for j in
                          rng.permutation(len(rest))[:max(0, k - 1)]]
    rows = np.zeros((len(picked), width), np.int32)
    spans = []
    for r, i in enumerate(picked):
        p = int(sched["prompt_len"][i])
        rows[r, :p] = sched["prompts"][i]
        rows[r, p:total[i]] = results[i].tokens
        spans.append((p, total[i]))
    return picked, rows, spans


def widest_gaps(ctx, weights, rows, spans, block: int = 4, lower=None):
    """The served tokens' gaps under the plain reference, all of them,
    and (``lower``) those of the tokens a lower precision puts first at
    the same positions."""
    served, picked = [], []
    for b in range(0, len(rows), block):
        g, gl = ctx.builder.served_gaps(ctx.config, weights,
                                        rows[b:b + block], lower)
        for r, (p, end) in enumerate(spans[b:b + block]):
            # position i scores token i + 1: the answer is p .. end - 1
            served.append(g[r, p - 1:end - 1])
            if gl is not None:
                picked.append(gl[r, p - 1:end - 1])
    cat = (lambda a: np.concatenate(a) if a else np.zeros((0,), np.float32))
    return cat(served), (cat(picked) if lower is not None else None)


def gap_checks(served, serve_cfg) -> list:
    """The two numbers that compare served tokens with the plain
    reference, each beside its limit: ``(name, value, limit, ok)``.
    ``served``: every compared token's gap under the reference's best.
    The run and ``tools/decode_readings.py`` (the control's readings)
    both get their verdict here."""
    mean = float(served.mean()) if served.size else float("inf")
    widest = float(served.max()) if served.size else float("inf")
    return [("mean_gap", mean, serve_cfg["mean_gap_limit"],
             mean <= serve_cfg["mean_gap_limit"]),
            ("widest_gap", widest, serve_cfg["gap_limit"],
             widest <= serve_cfg["gap_limit"])]


def report_checks(checks) -> None:
    """Each number compared beside its limit, as the run's last lines on
    standard error."""
    sys.stdout.flush()
    for name, value, limit, ok in checks:
        print(f"check {name}: {value} (limit {limit}) "
              f"{'ok' if ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()


def run(ctx) -> dict:
    import jax

    cfg, traffic, builder = ctx.config, ctx.traffic, ctx.builder
    sv = cfg["serve"]
    slots = int(sv["slots"])
    warmup_s = float(traffic["warmup_s"])

    # ---- weights, service, schedule: set-up
    t = time.perf_counter()
    weights = builder.draw_weights(cfg, ctx.seed)
    jax.block_until_ready(weights)
    t_w = time.perf_counter() - t
    svc = build_service(ctx, weights)
    traces_built = svc._trace_count
    t_s = time.perf_counter() - t - t_w
    sched = open_loop.schedule(traffic, ctx.seed, ctx.seconds,
                               cfg["vocab_size"])
    warm_buckets(svc, cfg["vocab_size"], ctx.seed)
    ctx.note(f"weights {t_w:.1f} s; service ({slots} slots, buckets "
             f"{list(svc.buckets)}, {traces_built} executables, strips "
             f"{svc.kv_bytes} bytes) {t_s:.1f} s; warm-up requests and "
             f"schedule {time.perf_counter() - t - t_w - t_s:.1f} s; "
             f"{sched['n']} requests at {traffic['rate_per_s']}/s, "
             f"prompts {int(sched['prompt_len'].sum())} and answers "
             f"{int(sched['output_len'].sum())} tokens in all")

    trace_dir = os.path.join(ctx.out_dir, "xplane")
    trace_s = min(float(traffic["trace_seconds"]), ctx.seconds / 2.0) \
        if ctx.trace else 0.0
    marks = {}

    # ---- the schedule: warm-up, then the window
    run_ = drive(svc, sched, warmup_s, ctx.seconds,
                 at_open=lambda: marks.update(open=ctx.clock.mark()),
                 trace_s=trace_s,
                 trace_start=lambda: start_trace(jax, trace_dir),
                 trace_stop=jax.profiler.stop_trace)
    compiles = ctx.clock.since(marks["open"])
    traces_end = svc._trace_count
    queue_at_close = svc.queue_depth()
    svc.stop(drain=False, timeout=120)
    t_open, t_close = run_["t_open"], run_["t_close"]
    setup_s = t_open - ctx.t0

    # ---- what became of the requests
    fate = settle(run_, sched)
    results = {i: run_["futures"][i].result() for i in fate["done"]}
    stamps = run_["stamps"]
    acc = open_loop.window_account(stamps, t_open, t_close)
    gaps_ms = [1e3 * g for g in acc["gaps_s"]]
    if not gaps_ms or acc["tokens"] < 1:
        raise lib.BenchFailure("no token and no gap inside the window")
    throughput = lib.rate(acc["tokens"], ctx.seconds)
    arrived = [i for i in range(sched["n"])
               if run_["sent_at"][i] is not None
               and t_open <= run_["sent_at"][i] < t_close]
    completed = [i for i in fate["done"]
                 if stamps[i] and t_open <= stamps[i][-1] < t_close]
    short = [i for i in fate["done"]
             if len(results[i].tokens) != int(sched["output_len"][i])
             or results[i].finish_reason != "length"
             or len(stamps[i]) != len(results[i].tokens)]
    ttft_ms = sorted(1e3 * (stamps[i][0] - run_["t_start"]
                            - float(sched["due_s"][i]))
                     for i in range(sched["n"])
                     if stamps[i] and t_open <= stamps[i][0] < t_close)
    late_ms = [1e3 * v for v in fate["late_s"]]
    late_mean = float(np.mean(late_ms)) if late_ms else float("inf")
    pct = {p: open_loop.nearest_rank(gaps_ms, p)
           for p in (50, 90, 95, 98, 99)}
    ordered = sorted(gaps_ms)
    tail = ordered[int(0.95 * len(ordered)):]
    ctx.note(f"window: {acc['tokens']} tokens, {len(gaps_ms)} gaps in "
             f"{ctx.seconds} s; {len(arrived)} requests arrived, "
             f"{len(completed)} completed, queue at close "
             f"{queue_at_close}; in the whole run {len(fate['done'])} "
             f"done, {len(fate['unfinished'])} cut by the stop, "
             f"{len(fate['failed'])} failed; set-up {setup_s:.1f} s")
    ctx.note(f"gaps between tokens, ms: {pct}; mean "
             f"{float(np.mean(gaps_ms)):.3f}; mean of the largest 5 % "
             f"{float(np.mean(tail)):.3f}; largest {ordered[-1]:.3f}")
    if ttft_ms:
        ctx.note(f"time to first token (from the due time; above the knee "
                 f"it reads the queue, printed and not judged), ms: "
                 f"median {ttft_ms[len(ttft_ms) // 2]:.1f}, 11th largest "
                 f"{ttft_ms[max(0, len(ttft_ms) - 11)]:.1f}, n "
                 f"{len(ttft_ms)}")
    ctx.note(f"generator lateness, ms: mean {late_mean:.3f}, worst "
             f"{max(late_ms, default=float('inf')):.3f} over "
             f"{len(late_ms)} sends")
    ctx.note(f"compiles inside the window: {compiles}; executables "
             f"built {traces_built}, at the end {traces_end}")
    for i, why in fate["failed"][:5]:
        ctx.note(f"FAILED request {i}: {why}")
    with open(os.path.join(ctx.out_dir, f"stamps.seed{ctx.seed}."
                           f"trace{int(ctx.trace)}.json"), "w") as f:
        json.dump({"t_open": t_open, "t_close": t_close,
                   "t_start": run_["t_start"], "t_trace": run_["t_trace"],
                   "due_s": sched["due_s"].tolist(),
                   "prompt_len": sched["prompt_len"].tolist(),
                   "output_len": sched["output_len"].tolist(),
                   "sent_at": run_["sent_at"], "stamps": stamps}, f)

    # ---- memory, then free the program's state
    step_mem = svc._step_exec.memory_analysis()
    program_bytes = int(step_mem.temp_size_in_bytes
                        + step_mem.argument_size_in_bytes
                        + step_mem.output_size_in_bytes
                        - step_mem.alias_size_in_bytes)
    runtime_peak = lib.memory_peak_bytes(
        jax, jax.devices()[:ctx.chips], 0)["runtime_peak_bytes_in_use"]
    ctx.note(f"the step's program, by the compiler: {program_bytes} bytes "
             f"(temporaries {int(step_mem.temp_size_in_bytes)}, arguments "
             f"{int(step_mem.argument_size_in_bytes)}, outputs "
             f"{int(step_mem.output_size_in_bytes)}); the runtime's peak "
             f"before the reference ran: {runtime_peak}")
    on_device = {d.platform for leaf in jax.tree_util.tree_leaves(
        (svc._params, svc._k, svc._v)) for d in leaf.devices()}
    win = {k: run_["marks"]["close"][k] - run_["marks"]["open"][k]
           for k in COUNTERS}
    stats = svc.stats()["decode"]
    svc._k = svc._v = svc._params = None
    svc._step_exec = svc._prefill_exec = svc._splice_exec = None
    del svc

    # ---- correct? the plain reference over a sample of the answers
    t = time.perf_counter()
    picked, rows, spans = sample_rows(
        sched, results, completed, int(traffic["check_requests"]),
        ctx.seed, int(sv["max_seq_len"]))
    served, _ = widest_gaps(ctx, weights, rows, spans)
    widest = float(served.max()) if served.size else float("inf")
    longest_total = max((int(sched["prompt_len"][i])
                         + int(sched["output_len"][i])
                         for i in completed), default=0)
    ctx.note(f"plain reference over {len(picked)} of the {len(completed)} "
             f"answers completed in the window ({served.size} served "
             f"tokens, the longest row {max((e for _p, e in spans), default=0)}"
             f" of the longest completed {longest_total}): "
             f"{time.perf_counter() - t:.1f} s; gap of a served token "
             f"under the reference's best: widest {widest:.6f}, 99th "
             f"percentile "
             f"{open_loop.nearest_rank(served.tolist(), 99.0) if served.size else None}"
             f", mean {float(served.mean()) if served.size else None}, "
             f"tokens that ARE the reference's best "
             f"{int((served == 0).sum())}")
    late_limit = float(traffic["lateness_limit_ms"])
    checks = gap_checks(served, sv) + [
        ("requests_failed", len(fate["failed"]), 0, not fate["failed"]),
        ("answers_not_of_full_length", len(short), 0, not short),
        ("answers_completed_in_window", len(completed), ">=1",
         len(completed) >= 1),
        ("generator_late_mean_ms", late_mean, late_limit,
         late_mean <= late_limit),
        ("compiles_in_window", compiles["backend_compiles"]
         + compiles["cache_misses"] + (traces_end - traces_built), 0,
         compiles["backend_compiles"] == 0
         and compiles["cache_misses"] == 0
         and traces_end == traces_built),
    ]
    if ctx.on_tpu:
        checks.append(("state_on", sorted(on_device), ["tpu"],
                       on_device == {"tpu"}))
    correct = all(ok for *_x, ok in checks)

    result = {
        "correct": bool(correct),
        "attempted": len(arrived),
        "failed": len(fate["failed"]),
        "end_to_end": {"decode_throughput": throughput,
                       "itl_p99_ms": pct[99], "setup_s": setup_s},
        "program_bytes": program_bytes,
        "observed": None,
        # (an infinite reading, nothing compared, is no JSON number)
        "checks": {name: {"value": str(value) if isinstance(value, float)
                          and not math.isfinite(value) else value,
                          "limit": limit}
                   for name, value, limit, _ok in checks},
    }
    if not ctx.trace:
        report_checks(checks)
        return result

    # ---- the traced run's observations, for the per-layer readers
    t_host_end = run_["t_trace"]
    host = open_loop.window_account(stamps, t_open, t_host_end)
    host_gaps_ms = [1e3 * g for g in host["gaps_s"]]
    # operations the host part's tokens needed: an answer token attends
    # over its prompt and the answer before it; a prompt counts where
    # its first token (the end of its prefill) falls inside
    flops = 0.0
    for i, row in enumerate(stamps):
        p = int(sched["prompt_len"][i])
        for j, ts in enumerate(row):
            if t_open <= ts < t_host_end:
                flops += builder.prompt_flops(cfg, p) if j == 0 else \
                    builder.flops_per_token(cfg, p + j)
    tr = time.perf_counter()
    xplane = trace_reduce.find_xplane(trace_dir)
    red = trace_reduce.reduce_file(xplane)
    events = trace_programs.module_events(xplane)
    programs = trace_programs.by_kind(events, PROGRAM_KINDS)
    ctx.note(f"xplane {os.path.getsize(xplane)} bytes reduced in "
             f"{time.perf_counter() - tr:.1f} s; programs {programs}; "
             f"idle by neighbours "
             f"{trace_programs.gap_totals(events, PROGRAM_KINDS)}")
    dev0 = red["devices"][0] if red["devices"] else {}
    # device seconds by what an instruction's result is shaped like: the
    # key/value strips, something vocabulary-wide, or the rest
    strip = (f"{slots},{cfg['n_head']},{sv['max_seq_len']},"
             f"{cfg['n_embd'] // cfg['n_head']}]")
    shaped = {"strips": 0.0, "vocabulary": 0.0, "rest": 0.0}
    for name, sec in dev0.get("op_self_s", {}).items():
        full = dev0["full_names"].get(name, name).split(" fusion(")[0]
        kind = "strips" if strip in full else \
            "vocabulary" if str(cfg["vocab_size"]) in full else "rest"
        shaped[kind] += sec
    ctx.note(f"device seconds by the shape of an instruction's result "
             f"(all programs, traced part): {shaped}")
    # key/value positions in use a step, over the traced part: a token
    # stamped there was decoded over its prompt and the answer before it
    traced_positions = sum(
        int(sched["prompt_len"][i]) + j
        for i, row in enumerate(stamps) for j, ts in enumerate(row)
        if j > 0 and t_host_end <= ts < t_close)
    traced_steps = (run_["marks"]["close"]["decode/steps"]
                    - run_["marks"]["trace"]["decode/steps"])
    positions_a_step = traced_positions / traced_steps \
        if traced_steps else None
    peaks = lib.peaks_for(jax.devices()[0].device_kind) \
        if ctx.on_tpu else None
    result["observed"] = {
        "decode_programs": programs,
        "device_busy_s": dev0.get("busy_s"),
        "decode_counters": {"slots": slots,
                            "steps": win["decode/steps"],
                            "active_slot_steps":
                                win["decode/active_slot_steps"],
                            "admissions": win["decode/admissions"]},
        "positions_in_use_a_step": positions_a_step,
        "needed_bytes_per_step": builder.needed_bytes_per_step(
            cfg, slots, positions_a_step)
        if positions_a_step is not None else None,
        "host_window_s": t_host_end - t_open,
        "itl_ms": {"p50": open_loop.nearest_rank(host_gaps_ms, 50.0),
                   "gaps": len(host_gaps_ms)} if host_gaps_ms else None,
        "host_needed_flops": flops,
        "host_tokens": host["tokens"],
        "peaks": peaks,
        "chips": ctx.chips,
    }
    ctx.note(f"host part of the window: {host['tokens']} tokens in "
             f"{t_host_end - t_open:.3f} s; counters over the window "
             f"{win}; traced part: {traced_steps} steps over "
             f"{positions_a_step} positions in use a step; the "
             f"service's own stats {stats}")
    result["device_busy"] = {"busy_s": red["busy_s"],
                             "window_s": red["window_s"]}
    result["breakdown"] = {
        "device_ops": [[n, s] for n, s in
                       trace_reduce.top(dev0.get("op_self_s", {}), 10,
                                        dev0.get("full_names"))],
        "idle_gaps": [[n, s] for n, s in
                      trace_programs.named_gaps(events, PROGRAM_KINDS, 10)],
        "itl_ms": {"median": pct[50], "p99": pct[99]},
        "ttft_ms": {"median": ttft_ms[len(ttft_ms) // 2],
                    "11th_largest": ttft_ms[-11]
                    if len(ttft_ms) >= 11 else None}
        if ttft_ms else None,
        "generator_late_ms": {"mean": late_mean,
                              "worst": max(late_ms, default=None)},
    }
    if not ctx.keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    report_checks(checks)
    return result
