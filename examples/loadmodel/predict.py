"""Load a pretrained model in any supported format and predict.

Mirror of the reference ``DL/example/loadmodel/`` (AlexNet +
``ModelValidator`` loading BigDL/Caffe/Torch models).  Demonstrates the
interop surface end-to-end: export a trained model to the BigDL protobuf
format and to a frozen TF GraphDef, reload both, and check the three
give identical predictions.
"""

from __future__ import annotations

import argparse
import os
import tempfile

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default=None,
                   help="path to a .bigdl model (default: train a fresh "
                        "LeNet on synthetic MNIST)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch, image, mnist
    from bigdl_tpu.interop import (load_bigdl_module, load_tf_graph,
                                   save_bigdl_module, save_tf_graph)
    from bigdl_tpu.models.lenet import lenet5

    if args.model:
        model = load_bigdl_module(args.model)
    else:
        imgs, lbls = mnist.synthetic_mnist(1024)
        ds = (DataSet.array(mnist.to_samples(imgs, lbls))
              >> image.BytesToGreyImg()
              >> image.GreyImgNormalizer(mnist.TRAIN_MEAN, mnist.TRAIN_STD)
              >> SampleToMiniBatch(128))
        model = lenet5(class_num=10)
        (optim.LocalOptimizer(model, ds, nn.ClassNLLCriterion())
         .set_optim_method(optim.SGD(learning_rate=0.05, momentum=0.9,
                                     dampening=0.0))
         .set_end_when(optim.max_epoch(2))).optimize()

    model.training = False
    x = np.random.RandomState(0).rand(4, 1, 28, 28).astype(np.float32)
    ref = np.argmax(np.asarray(model.forward(x)), -1)

    tmp = tempfile.mkdtemp()
    bigdl_path = os.path.join(tmp, "model.bigdl")
    save_bigdl_module(model, bigdl_path)
    m1 = load_bigdl_module(bigdl_path)
    m1.training = False
    p1 = np.argmax(np.asarray(m1.forward(x)), -1)

    tf_path = os.path.join(tmp, "model.pb")
    inp, out = save_tf_graph(model, tf_path, input_shape=(4, 1, 28, 28))
    m2 = load_tf_graph(tf_path, inputs=[inp], outputs=[out])
    p2 = np.argmax(np.asarray(m2.forward(x)), -1)

    assert (ref == p1).all() and (ref == p2).all(), (ref, p1, p2)
    print(f"predictions agree across native/bigdl/tf formats: {ref}")


if __name__ == "__main__":
    main()
