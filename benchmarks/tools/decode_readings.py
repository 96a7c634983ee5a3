#!/usr/bin/env python3
"""What a builder reads on the chip before a ``decode`` cell's numbers
are written down, many windows in ONE process (the model is built and
the service compiled once):

    python3 benchmarks/tools/decode_readings.py --workload <cell> \
        --out chiprun_out/x sweep --rates 2.0,2.2,2.4 --seeds 1,2,3
    python3 benchmarks/tools/decode_readings.py --workload <cell> \
        --out chiprun_out/x gaps --seeds 1,2,...,12 --seconds 8

``sweep``: for each rate and seed one window of the cell's own traffic
at that rate, through the runner's own ``drive``; per window the
requests that arrived in it, those completed in it, tokens/s and the
gaps' percentiles.  The knee is the highest rate at which every seed
completed at least 97 % of what arrived.

``gaps``: for each seed NEW weights in the service (its executables take
them as arguments) and one short window at the cell's rate; then the
plain reference over the runner's own sample of the completed answers:
the mean and the widest gap of a served token (the program's reading)
beside those of the token that the reference computed in bfloat16 puts
first at the same positions (the control's reading), each with the
verdict of the runner's own ``gap_checks`` (``program_correct``,
``bf16_correct`` and the names of the checks each failed).  A limit goes
between the largest of the first and the smallest of the second.

One JSON line a window goes to ``<out>/<mode>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib, open_loop  # noqa: E402


def wait_all(futures, timeout_s: float = 180.0) -> None:
    end = time.perf_counter() + timeout_s
    for f in futures:
        if f is not None:
            try:
                f.result(timeout=max(0.1, end - time.perf_counter()))
            except Exception:
                pass


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("mode", choices=("sweep", "gaps"))
    p.add_argument("--rates", default="")
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=None)
    args = p.parse_args(argv)

    bench = lib.load_benchmark(held=True)
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    seconds = args.seconds or float(bench["run_seconds"])
    config = lib.with_tiny(lib.load_json("configs", cell["config"]),
                           args.tiny)
    traffic = lib.with_tiny(lib.load_json("traffic", cell["traffic"]),
                            args.tiny)
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np
    if not args.tiny and jax.devices()[0].platform != "tpu":
        raise lib.BenchFailure("readings are taken on the chip")
    lib.enable_compile_cache(jax)
    runner = lib.load_module("runners", traffic["runner"])
    builder = lib.load_module("builders", config["builder"])
    ctx = types.SimpleNamespace(config=config, traffic=traffic,
                                builder=builder, seconds=seconds)
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, args.mode + ".jsonl"), "a")

    def emit(row):
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    t = time.perf_counter()
    weights = builder.draw_weights(config, seeds[0])
    svc = runner.build_service(ctx, weights)
    runner.warm_buckets(svc, config["vocab_size"], seeds[0])
    print(f"service ready in {time.perf_counter() - t:.1f} s", flush=True)
    model = builder.build_model(config)

    def window(seed, rate, secs):
        tr = dict(traffic, rate_per_s=rate)
        sched = open_loop.schedule(tr, seed, secs, config["vocab_size"])
        run = runner.drive(svc, sched, float(tr["warmup_s"]), secs)
        t_drain = time.perf_counter()
        wait_all(run["futures"])
        fate = runner.settle(run, sched)
        acc = open_loop.window_account(run["stamps"], run["t_open"],
                                       run["t_close"])
        gaps = [1e3 * g for g in acc["gaps_s"]]
        stamps = run["stamps"]
        arrived = sum(1 for s in run["sent_at"] if s is not None
                      and run["t_open"] <= s < run["t_close"])
        completed = [i for i in fate["done"] if stamps[i]
                     and run["t_open"] <= stamps[i][-1] < run["t_close"]]
        ordered = sorted(gaps)
        row = {"seed": seed, "rate": rate, "seconds": secs,
               "n": sched["n"], "arrived": arrived,
               "completed": len(completed),
               "failed": len(fate["failed"]),
               "drain_s": time.perf_counter() - t_drain,
               "tokens_per_s": acc["tokens"] / secs, "gaps": len(gaps),
               "late_mean_ms": 1e3 * float(np.mean(fate["late_s"])),
               "itl_ms": {str(q): open_loop.nearest_rank(gaps, q)
                          for q in (50, 90, 95, 98, 99)},
               "itl_mean_ms": float(np.mean(gaps)),
               "itl_tail5_mean_ms": float(np.mean(
                   ordered[int(0.95 * len(ordered)):]))}
        return row, sched, run, fate, completed

    if args.mode == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            for seed in seeds:
                row, *_ = window(seed, rate, seconds)
                row["ratio"] = row["completed"] / max(1, row["arrived"])
                emit(row)
    else:
        rate = float(traffic["rate_per_s"])
        for n, seed in enumerate(seeds):
            if n:
                # new weights for this seed, in place of the old
                svc._params = None
                weights = None
                weights = builder.draw_weights(config, seed)
                svc._params = builder.product_params(config, model,
                                                     weights)
            row, sched, run, fate, completed = window(seed, rate, seconds)
            results = {i: run["futures"][i].result() for i in fate["done"]}
            picked, rows, spans = runner.sample_rows(
                sched, results, completed,
                int(traffic["check_requests"]), seed,
                int(config["serve"]["max_seq_len"]))
            t = time.perf_counter()
            served, low = runner.widest_gaps(ctx, weights, rows, spans,
                                             lower=jnp.bfloat16)
            row.update({
                "reference_s": time.perf_counter() - t,
                "rows": len(picked), "served_tokens": int(served.size),
                "program_widest_gap": float(served.max()),
                "program_p99_gap": open_loop.nearest_rank(
                    served.tolist(), 99.0),
                "program_mean_gap": float(served.mean()),
                "program_tokens_not_best": int((served > 0).sum()),
                "bf16_widest_gap": float(low.max()),
                "bf16_p99_gap": open_loop.nearest_rank(low.tolist(), 99.0),
                "bf16_mean_gap": float(low.mean()),
                "bf16_tokens_not_best": int((low > 0).sum())})
            for side, gaps in (("program", served), ("bf16", low)):
                failed = [name for name, _v, _l, ok in
                          runner.gap_checks(gaps, config["serve"])
                          if not ok]
                row[side + "_correct"] = not failed
                row[side + "_failed_checks"] = failed
            emit(row)
    svc.stop(drain=False, timeout=60)
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
