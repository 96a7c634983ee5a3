#!/usr/bin/env python3
"""The two readings a ``train_lm`` cell's ``param_change_tol`` limits
are set between, on the chip (or ``--tiny`` on the CPU), for each seed,
both through the RUNNER'S OWN comparison (``param_change_errors`` leaf
by leaf, ``params_correct`` against the configuration's limits):

1. ``product``: what the product gives against the plain reference in
   the cell's stated arithmetic.  It has to come out correct;
2. ``state_bf16``: what the plain reference gives when computed ONE
   PRECISION BELOW the stated one, against the reference in the stated
   arithmetic: bf16 wherever f32 is stated (norms, softmax, router,
   recurrence; criterion and update stay f32), which makes the whole of
   the computation bf16.  It has to come out NOT correct.  (A control
   with the products' operands rounded to ``float8_e4m3fn`` was dropped:
   e4m3 cannot hold a gradient of 1e-4, every leaf read exactly 1, the
   state-left-unchanged fault and no reading of a precision.)

    python3 benchmarks/tools/precision_reading.py --workload <cell> \
        --seeds 3200000101,3200000103 [--steps 1,3] [--lrs 0.01,1] \
        [--out DIR] [--tiny]

``--steps`` and ``--lrs`` read after other numbers of steps than the
traffic's ``check_losses`` and at other learning rates than the
configuration's ``check_lr``.  One process, one chip; prints one JSON
line for each seed, number of steps and rate (per kind of leaf the
worst leaf's error, and ``correct``), and writes every leaf's error
under ``--out``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402

def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--steps", default=None)
    p.add_argument("--lrs", default=None)
    p.add_argument("--out", default=None)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    cell = {w["name"]: w for w in lib.load_benchmark()["workloads"]}[
        args.workload]
    cfg = lib.with_tiny(lib.load_json("configs", cell["config"]), args.tiny)
    traffic = lib.with_tiny(lib.load_json("traffic", cell["traffic"]),
                            args.tiny)
    import jax
    import jax.numpy as jnp
    import numpy as np
    lib.enable_compile_cache(jax)
    runner = lib.load_module("runners", traffic["runner"])
    builder = lib.load_module("builders", cfg["builder"])
    tr = cfg["train"]
    batch = tr["batch_per_chip"]
    steps = [int(n) for n in (args.steps or
                              str(traffic["check_losses"])).split(",")]
    lrs = [float(v) for v in (args.lrs or str(tr.get(
        "check_lr", tr["optimizer"]["lr"]))).split(",")]
    model = builder.build_model(cfg)
    stated = builder.reference_step(cfg, jnp.bfloat16)
    controls = {"state_bf16": builder.reference_step(
        cfg, jnp.bfloat16, state_dtype=jnp.bfloat16)}
    for seed in (int(s) for s in args.seeds.split(",")):
        samples = builder.make_samples(cfg, seed, batch, max(steps))
        for n, lr in ((n, lr) for n in steps for lr in lrs):
            t = time.perf_counter()
            out = {"seed": seed, "steps": n, "lr": lr,
                   "device": lib.device_stamp(jax)}
            out["stated_losses"], initial, reference, out["draw_s"] = \
                runner.reference_run(seed, model, stated, samples, batch, n,
                                     lr)
            out["stated_s"] = time.perf_counter() - t
            # how far the reference moved each kind, against its size:
            # a leaf moved by a few ulps compares as noise
            out["moved_by_kind"] = {}
            for path, ref in reference.items():
                kind = runner.leaf_kind(path)
                moved = float(np.linalg.norm(ref - initial[path])
                              / (np.linalg.norm(initial[path]) or 1.0))
                out["moved_by_kind"][kind] = min(
                    moved, out["moved_by_kind"].get(kind, moved))
            got = {}
            out["product_losses"], got["product"] = runner.product_run(
                cfg, builder, model, samples, seed, n, lr)
            for name, step in controls.items():
                out[name + "_losses"], _, got[name], _ = \
                    runner.reference_run(seed, model, step, samples, batch,
                                         n, lr)
            for name, sample in got.items():
                errors = runner.param_change_errors(initial, reference,
                                                    sample)
                ok, _ = runner.params_correct(errors,
                                              tr["param_change_tol"])
                out[name] = {
                    "correct": ok,
                    "one_norm": runner.global_error(initial, reference,
                                                    sample),
                    "worst_by_kind": {
                        kind: err for kind, (err, _) in
                        runner.worst_by_kind(errors).items()}}
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    leaves = f"{name}.seed{seed}.steps{n}.lr{lr}.json"
                    with open(os.path.join(args.out, leaves), "w") as f:
                        json.dump(errors, f, indent=0)
            out["seconds"] = time.perf_counter() - t
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
