#!/usr/bin/env python3
"""Several runs of cells in one call, one process each, one after the
other (a chip belongs to one process at a time; this parent never
touches JAX).  Each run's whole output goes to ``<out>/<tag>.log`` and
its last line to ``<out>/results.jsonl``; at the end, per cell and
metric, the median and the spread the bounds are set from.

    python3 benchmarks/tools/runs.py --out chiprun_out/r1 \
        ptb-medium-train-1chip:0:1001,1002,1003 ...

A spec is ``<workload>:<trace>:<seed>,<seed>,...``; ``--seconds`` is
passed on when given.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402  (imports no JAX)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("--seconds", default=None)
    p.add_argument("--keep-trace", action="store_true")
    p.add_argument("specs", nargs="+")
    args = p.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    rows = []
    failed = 0
    for spec in args.specs:
        workload, trace, seeds = spec.split(":")
        for seed in seeds.split(","):
            tag = f"{workload}.t{trace}.s{seed}.{len(rows)}"
            cmd = command + ["--workload", workload, "--seed", seed,
                             "--trace", trace,
                             "--out", os.path.join(args.out, "bench_out")]
            if args.seconds:
                cmd += ["--seconds", args.seconds]
            if args.keep_trace:
                cmd += ["--keep-trace"]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
            wall = time.time() - t0
            with open(os.path.join(args.out, tag + ".log"), "w") as f:
                f.write(proc.stdout)
            last = proc.stdout.strip().splitlines()[-1] \
                if proc.stdout.strip() else ""
            row = {"workload": workload, "trace": int(trace),
                   "seed": int(seed), "rc": proc.returncode,
                   "wall_s": round(wall, 1)}
            try:
                row["result"] = json.loads(last) \
                    if proc.returncode == 0 else None
            except ValueError:
                row["result"] = None
            if row["result"] is None:
                failed += 1
                print(f"FAILED {tag} rc={proc.returncode}:\n"
                      + "\n".join(proc.stdout.splitlines()[-25:]),
                      flush=True)
            else:
                r = row["result"]
                print(f"{tag} wall {wall:.0f}s correct={r['correct']} "
                      f"attempted={r['attempted']} failed={r['failed']} "
                      + " ".join(f"{k}={v['value']:.6g}"
                                 for k, v in r["metrics"].items())
                      + f" mem={r['device']['memory_peak_bytes']}"
                      + (f" busy={r['device'].get('busy_s')}"
                         f"/{r['device'].get('window_s')}"
                         if int(trace) else ""), flush=True)
            rows.append(row)
            with open(os.path.join(args.out, "results.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
    # spreads, per (workload, trace) group in the order given
    groups: dict = {}
    for row in rows:
        if row["result"]:
            groups.setdefault((row["workload"], row["trace"]),
                              []).append(row["result"])
    for (workload, trace), results in groups.items():
        names = results[0]["metrics"].keys()
        for name in names:
            vals = [r["metrics"][name]["value"] for r in results
                    if name in r["metrics"]]
            line = (f"{workload} trace={trace} {name}: n={len(vals)} "
                    f"median={statistics.median(vals):.6g}")
            if len(vals) >= 3:
                line += f" iqr/median={lib.iqr_spread(vals):.4%}"
            if name == "setup_s" and len(vals) > 1:
                line += (f" first={vals[0]:.2f} median-of-rest="
                         f"{statistics.median(vals[1:]):.2f}")
            line += " values=" + ",".join(f"{v:.6g}" for v in vals)
            print(line, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
