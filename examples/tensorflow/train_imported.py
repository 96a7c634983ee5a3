"""TensorFlow interop end-to-end: export → import → TRAIN the imported
graph.

Mirror of the reference ``DL/example/tensorflow/`` (``loadandsave`` +
``transferlearning``): a model crosses the TF GraphDef boundary in both
directions and the re-imported graph trains through the Optimizer via
``TFSession.train`` (reference ``utils/tf/Session.scala:111``).
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true")
    p.add_argument("-e", "--max-epoch", type=int, default=4)
    p.add_argument("-b", "--batch-size", type=int, default=32)
    p.add_argument("--queue-fed", action="store_true",
                   help="also demo training a GraphDef whose TFRecord "
                        "input pipeline is baked into the graph")
    args = p.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np
    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.interop import load_tf_graph, save_tf_graph
    from bigdl_tpu.interop.session import TFSession

    # 1) SAVE: a trained-ish model exits as a frozen GraphDef
    model = nn.Sequential(nn.Linear(4, 16), nn.ReLU(),
                          nn.Linear(16, 3), nn.LogSoftMax())
    model.initialize(0)
    tmp = tempfile.mkdtemp(prefix="tf_example_")
    pb = os.path.join(tmp, "model.pb")
    # trainable=True: weights exported as VariableV2 (not frozen Consts)
    # so the re-imported graph can TRAIN (Session.train path)
    save_tf_graph(model, pb, input_shape=(1, 4), trainable=True)
    print(f"saved GraphDef: {pb} ({os.path.getsize(pb)} bytes)")

    # 2) LOAD: the GraphDef comes back as an executable module
    m = load_tf_graph(pb, inputs=["input"], outputs=["output"])
    x_check = np.random.RandomState(0).rand(2, 4).astype(np.float32)
    ref = np.asarray(model.forward(x_check))
    got = np.asarray(m.forward(x_check))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    print("reload parity: OK")

    # 3) TRAIN the imported graph (Session.train analog): synthetic
    # 3-class blobs
    rng = np.random.RandomState(1)
    centers = rng.randn(3, 4) * 3
    yb = rng.randint(0, 3, 512)
    xb = (centers[yb] + rng.randn(512, 4)).astype(np.float32)
    ds = (DataSet.array([Sample(x, np.int32(t)) for x, t in zip(xb, yb)])
          >> SampleToMiniBatch(args.batch_size))
    sess = TFSession(pb, inputs=["input"], outputs=["output"])
    sess.train(ds, nn.ClassNLLCriterion(),
               optim_method=optim.Adam(learning_rate=0.05),
               end_when=optim.max_epoch(args.max_epoch))
    out = np.asarray(sess.run(xb))
    acc = float((out.argmax(1) == yb).mean())
    print(f"final: train_acc={acc:.4f}")

    # 4) QUEUE-FED: a GraphDef whose input pipeline (TFRecord reader ->
    # decode -> example queue) is baked into the graph trains with NO
    # external dataset — the pipeline is detected and replayed
    # host-side (reference Session.scala:111-165)
    if args.queue_fed:
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "..", "tests"))
        from tfgraph_util import build_queue_graph
        from bigdl_tpu.dataset import tfrecord

        true_w = np.float32([1.0, -2.0, 3.0, 0.5])
        rng2 = np.random.default_rng(0)
        recs = []
        for _i in range(64):
            x = rng2.normal(0, 1, 4).astype(np.float32)
            recs.append(np.concatenate([x, [x @ true_w]]).astype(
                np.float32).tobytes())
        rec_path = os.path.join(tmp, "train.tfrecord")
        tfrecord.write_records(rec_path, recs)
        qpb = os.path.join(tmp, "queue_graph.pb")
        with open(qpb, "wb") as f:
            f.write(build_queue_graph(rec_path))
        qsess = TFSession(qpb, outputs=["loss"])  # inputs auto-detected
        losses = qsess.train(optim_method=optim.SGD(learning_rate=0.1),
                             epochs=args.max_epoch * 5)
        print(f"queue-fed: loss {losses[0]:.4f} -> {losses[-1]:.6f} "
              f"({len(losses)} steps, pipeline batch "
              f"{qsess.pipeline.batch_size})")


if __name__ == "__main__":
    main()
