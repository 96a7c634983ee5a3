#!/usr/bin/env python3
"""Records the small trace that ``test_trace_reduce.py`` checks the
reduction on: a few executions of a tiny jitted program that holds a
loop (so that events nest) on whatever device JAX has — made on the
chip once, in one of PR 23's proving calls.

    python3 benchmarks/tests/record_trace.py <out_dir>
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def tiny_step(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=4)
        return w - 0.01 * (x.T @ h), jnp.sum(h)

    w = jnp.ones((128, 128), jnp.float32) * 0.01
    x = jnp.ones((128, 128), jnp.float32)
    w, s = tiny_step(w, x)
    jax.block_until_ready(s)
    tmp = os.path.join(out_dir, "tmp_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=options)
    for i in range(5):
        w, s = tiny_step(w, x)
        jax.block_until_ready(s)
        if i == 2:
            time.sleep(0.02)  # one idle gap that stands out
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                    recursive=True)[0]
    dst = os.path.join(out_dir, "tiny_step.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{dst}: {os.path.getsize(dst)} bytes on "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
