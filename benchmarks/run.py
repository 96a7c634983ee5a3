#!/usr/bin/env python3
"""One cell, once, in a new process:

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

loads, warms up, measures for ``--seconds`` and prints as its LAST line
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, in a traced run ``breakdown``, and last ``checks`` where the
runner gives the numbers it compared.  ``--trace 0`` gives the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.  What
else it has to say goes on earlier lines and into
``<--out>/<workload>/``.

The cell's configuration, builder, traffic mix, runner and per-layer
readers are files found by the names in ``BENCHMARK.json`` (README.md
beside this file).  Off the TPU, or with fewer chips than the cell asks
for, it raises and prints no result — except with ``--tiny``, the CPU
rehearsal at toy sizes, whose last line says ``"correct": false``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python can stamp it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal at toy sizes; never correct: true")
    p.add_argument("--out", default=os.path.join(ROOT, "bench_out"),
                   help="directory for facts, traces and notes")
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the xplane file in the output directory")
    args = p.parse_args(argv)

    # held cells too (benchmarks/held/): the driver asks for none of them
    bench = lib.load_benchmark(held=True)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise lib.BenchFailure(f"no workload {args.workload!r} in "
                               f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[args.workload]
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    config = lib.with_tiny(lib.load_json("configs", cell["config"]),
                           args.tiny)
    traffic = lib.with_tiny(lib.load_json("traffic", cell["traffic"]),
                            args.tiny)

    import jax
    from bigdl_tpu.engine import Engine
    stamp = lib.device_stamp(jax)
    on_tpu = stamp["platform"] == "tpu"
    if not args.tiny and (not on_tpu or stamp["count"] != cell["chips"]):
        raise lib.BenchFailure(
            f"{args.workload} needs {cell['chips']} TPU chip(s); JAX "
            f"found {stamp}.  No fallback (CPU rehearsal: --tiny)")
    if args.tiny and jax.device_count() < cell["chips"]:
        raise lib.BenchFailure(
            f"the rehearsal of a {cell['chips']}-chip cell needs "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    if not on_tpu:
        # "auto" means xla off the TPU: engage the kernels, which then
        # run under the Pallas interpreter
        Engine.set_kernel_impl("pallas")
    cache_dir = lib.enable_compile_cache(jax)
    out_dir = os.path.join(args.out, args.workload)
    os.makedirs(out_dir, exist_ok=True)
    print(f"{args.workload}: seed {args.seed}, {seconds} s, trace "
          f"{args.trace}{', TINY rehearsal' if args.tiny else ''}; jax "
          f"{jax.__version__}; device {stamp}; compile cache {cache_dir}",
          flush=True)

    clock = lib.CompileClock(jax)
    ctx = types.SimpleNamespace(
        workload=args.workload, config=config, traffic=traffic,
        builder=lib.load_module("builders", config["builder"]),
        chips=cell["chips"], seed=args.seed, seconds=seconds,
        trace=bool(args.trace), tiny=args.tiny, on_tpu=on_tpu,
        keep_trace=args.keep_trace, t0=T0, clock=clock,
        out_root=args.out, out_dir=out_dir,
        note=lambda msg: print("   " + msg, flush=True))
    try:
        result = lib.load_module("runners", traffic["runner"]).run(ctx)
    finally:
        clock.close()
    print(f"   compile events in the whole run: {clock.mark()}",
          flush=True)

    memory = lib.memory_peak_bytes(jax, jax.devices()[:cell["chips"]],
                                   result["program_bytes"])
    print(f"   memory: {memory}", flush=True)
    device = dict(stamp, memory_peak_bytes=memory["memory_peak_bytes"])
    metrics = {}
    if args.trace:
        device.update(result["device_busy"])
        for m in lib.metrics_for(bench, "per_layer", args.workload):
            value = lib.load_module("layer_metrics", m["name"]).read(
                result["observed"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in lib.metrics_for(bench, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": result["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {
        # a rehearsal can never be taken for a result
        "correct": bool(result["correct"]) and not args.tiny,
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": device}
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    if result.get("checks"):
        # each number compared beside its limit, last on the line
        line["checks"] = result["checks"]
    if args.tiny:
        print(f"   rehearsal: the run's own checks said correct="
              f"{result['correct']}", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
