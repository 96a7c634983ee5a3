#!/usr/bin/env python3
"""What a freshly drawn router sends the held experts, at the cell's
own sizes: the reading a ``train_lm`` cell's ``row_factor`` is set from
(configs/zaya1-8b-share2.json, ``assumed.rows``).

    python3 benchmarks/tools/router_load.py --workload <cell> \
        --seeds 3500000101,3500000102,... [--tiny]

For each seed: the model drawn as the optimizer draws it, one step's
records from the builder, ONE forward pass in the cell's compute dtype
with ``row_factor`` 2.0 (with one expert a token that buffer holds every
assignment), and per layer the held experts' load over their balanced
load, read from the model's own ``rows_by_expert`` counters.  A count;
prints one JSON line a seed and the largest ratio of all."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    cell = {w["name"]: w for w in lib.load_benchmark()["workloads"]}[
        args.workload]
    cfg = lib.with_tiny(lib.load_json("configs", cell["config"]), args.tiny)
    cfg["train"]["row_factor"] = 2.0
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu.utils.precision import cast_floating
    lib.enable_compile_cache(jax)
    builder = lib.load_module("builders", cfg["builder"])
    runner = lib.load_module("runners", "train")
    model = builder.build_model(cfg)
    tr = cfg["train"]
    batch, tokens = tr["batch_per_chip"], tr["batch_per_chip"] * tr["seq_len"]
    e = model.layers[0].experts
    balanced = tokens * e.top_k * e.n_held / e.n_experts
    dtype = jnp.dtype(tr["compute_dtype"] or "float32")
    forward = jax.jit(lambda p, s, x: model.apply(
        cast_floating(p, dtype), s, x, training=True)[1])
    worst = 0.0
    for seed in (int(s) for s in args.seeds.split(",")):
        params, state = model.init(
            jax.random.split(jax.random.PRNGKey(seed))[1])
        x, _ = runner.stack_batch(
            builder.make_samples(cfg, seed, batch, 1), 0, batch)
        counts = model.expert_counts(forward(params, state, x))
        held = [c["rows_held"] / balanced for c in counts]
        top = [max(c["rows_by_expert"]) * e.n_held / balanced
               for c in counts]
        worst = max(worst, *held)
        print(json.dumps({
            "seed": seed, "device": lib.device_stamp(jax),
            "balanced": balanced,
            "held_over_balanced_by_layer": [round(v, 4) for v in held],
            "fullest_expert_over_its_share_by_layer":
                [round(v, 3) for v in top]}), flush=True)
        for leaf in jax.tree_util.tree_leaves((params, state)):
            leaf.delete()
    print(json.dumps({"largest_held_over_balanced": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
