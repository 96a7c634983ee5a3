#!/usr/bin/env python3
"""Records the small trace that ``test_host_spans.py`` checks the span
reduction on: a tiny model driven by ``LocalOptimizer`` under telemetry
(K=2, blocks of two 64-row batches) on whatever device JAX has, with the
profiler over a dozen steady steps — made on the chip once, in one of
PR 24's calls.  One mini-batch inside the traced window takes 30 ms to
arrive, so that the longest idle gap of the device lies inside a
``batch_pull`` span.

What the runtime itself writes on the host plane (its own TraceMes, some
hundred KB) is cut away: the file keeps the device planes whole and, of
the host plane, the ``bigdl:`` events — which is all the reduction reads.

    python3 benchmarks/tests/record_spans.py <out_dir>
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

K = 2
BATCH = 64
TRACE_FROM, TRACE_TO = 8, 20      # steps; both block ends
SLOW_BATCH, SLOW_S = 13, 0.030    # pulled while staging block 6


def prune(src: str, dst: str) -> None:
    """Copy an xplane file without the host plane's non-``bigdl:``
    events (and the lines and event names left unused)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        keep = {i for i, m in plane.event_metadata.items()
                if m.name.startswith("bigdl:")}
        for line in plane.lines:
            for i in reversed(range(len(line.events))):
                if line.events[i].metadata_id not in keep:
                    del line.events[i]
        for i in reversed(range(len(plane.lines))):
            if not plane.lines[i].events:
                del plane.lines[i]
        for i in [i for i in plane.event_metadata if i not in keep]:
            del plane.event_metadata[i]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


def main(out_dir: str) -> None:
    import jax
    import numpy as np
    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.dataset.transformer import Transformer
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    class OneSlowBatch(Transformer):
        """Holds one mini-batch of the stream back for a while."""

        def __init__(self):
            self.seen = 0

        def __call__(self, it):
            for batch in it:
                if self.seen == SLOW_BATCH:
                    time.sleep(SLOW_S)
                self.seen += 1
                yield batch

    tmp = os.path.join(out_dir, "tmp_trace")

    class TraceSteps:
        """The train-summary surface: the profiler runs from the replay
        of step TRACE_FROM to the replay of step TRACE_TO."""

        def add_train_step(self, step, loss, lr, throughput):
            if step == TRACE_FROM:
                shutil.rmtree(tmp, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(tmp, profiler_options=options)
            elif step == TRACE_TO:
                jax.profiler.stop_trace()

        def add_scalar(self, *a, **k):
            pass

        def trigger_for(self, name):
            return None

    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (256,)).astype(np.float32),
                      np.int32(i % 10)) for i in range(BATCH * 8)]
    model = (nn.Sequential()
             .add(nn.Linear(256, 256)).add(nn.ReLU())
             .add(nn.Linear(256, 10)).add(nn.LogSoftMax()))
    ds = DataSet.array(samples) >> SampleToMiniBatch(BATCH) >> OneSlowBatch()
    opt = (LocalOptimizer(model, ds, nn.ClassNLLCriterion())
           .set_optim_method(optim.SGD(1e-2))
           .set_steps_per_dispatch(K)
           .set_train_summary(TraceSteps())
           .set_end_when(optim.max_iteration(TRACE_TO))
           .set_telemetry(True))
    opt.optimize()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                    recursive=True)[0]
    dst = os.path.join(out_dir, "tiny_spans.xplane.pb")
    prune(src, dst)
    print(f"{dst}: {os.path.getsize(dst)} bytes (recorded: "
          f"{os.path.getsize(src)}) on {jax.devices()[0].device_kind}; "
          f"{opt._dispatch_count} blocks")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
