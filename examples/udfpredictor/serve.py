"""Concurrent inference service demo — dynamic batching engine.

Mirror of the reference ``DL/example/udfpredictor/`` (a Spark-SQL UDF
serving text classification through a shared model).  Spark UDFs map to
concurrent caller threads sharing one
:class:`bigdl_tpu.serving.InferenceService`: the engine coalesces their
single-row requests into bucket-padded AOT-compiled dispatches, so N
callers cost ~N/max_batch_size device forwards instead of N.

Run (CPU demo):
    python examples/udfpredictor/serve.py --cpu --threads 16
"""

from __future__ import annotations

import argparse
from concurrent.futures import ThreadPoolExecutor

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=256)
    p.add_argument("--threads", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--timeout-ms", type=float, default=2.0)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.serving import InferenceService

    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                          nn.Linear(32, 4), nn.SoftMax())
    model.initialize(rng=0)

    # deploy-time AOT warmup: every power-of-two row bucket compiles
    # HERE, so no request ever pays a compile (stats prove it below)
    service = InferenceService(model, input_spec=((16,), np.float32),
                               max_batch_size=args.max_batch,
                               batch_timeout_ms=args.timeout_ms,
                               name="udfpredictor")

    rng = np.random.RandomState(0)
    requests = [rng.rand(1, 16).astype(np.float32)
                for _ in range(args.requests)]

    with ThreadPoolExecutor(max_workers=args.threads) as pool:
        results = list(pool.map(service.predict, requests))

    # deterministic model ⇒ identical request → identical answer
    again = service.predict(requests[0])
    assert np.array_equal(results[0], again)

    stats = service.stats()
    service.stop()
    probs = np.concatenate(results)
    lat = stats["latency_ms"] or {}
    print(f"served {len(results)} requests on {args.threads} threads; "
          f"mean top-prob {probs.max(-1).mean():.3f}")
    print(f"p95 latency {lat.get('p95', float('nan')):.2f} ms "
          f"(p50 {lat.get('p50', float('nan')):.2f} ms), "
          f"batch occupancy {stats['mean_batch_occupancy']:.2f}, "
          f"{stats['dispatch_count']} dispatches for "
          f"{stats['requests_completed']} rows, "
          f"{stats['compile_count']} compiles (all at warmup)")


if __name__ == "__main__":
    main()
