"""LeNet-5 on a SINGLE process via LocalOptimizer — the mirror of the
reference ``DL/example/lenetLocal/{Train,Test,Predict}.scala`` trio
(BigDL without Spark: ``bigdl.localMode=true``).

Covers the whole local loop in one script: train, checkpoint, reload,
evaluate (Top1), and predict a few samples.

Usage:
    python examples/lenetLocal/train.py [-f MNIST_DIR] [-b N] [-e N]
        [--checkpoint DIR] [--cpu]
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
    p = argparse.ArgumentParser(description="LeNet5 local training")
    p.add_argument("-f", "--folder", default=None)
    p.add_argument("-b", "--batch-size", type=int, default=128)
    p.add_argument("-e", "--max-epoch", type=int, default=2)
    p.add_argument("--learning-rate", type=float, default=0.05)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--synthetic-n", type=int, default=2048)
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
    from bigdl_tpu.interop import load_bigdl_module, save_bigdl_module
    from bigdl_tpu.models.lenet import lenet5
    from bigdl_tpu.optim.predictor import Evaluator, Predictor

    if args.folder:
        imgs, lbls = mnist.load_mnist(args.folder, train=True)
        vimgs, vlbls = mnist.load_mnist(args.folder, train=False)
    else:
        imgs, lbls = mnist.synthetic_mnist(args.synthetic_n)
        vimgs, vlbls = mnist.synthetic_mnist(512, seed=7)

    def pipeline(imgs, lbls, train):
        return (DataSet.array(mnist.to_samples(imgs, lbls))
                >> image.BytesToGreyImg()
                >> image.GreyImgNormalizer(mnist.TRAIN_MEAN,
                                           mnist.TRAIN_STD)
                >> SampleToMiniBatch(args.batch_size,
                                     drop_remainder=train))

    model = lenet5(class_num=10)
    criterion = nn.ClassNLLCriterion()
    optimizer = (optim.LocalOptimizer(model, pipeline(imgs, lbls, True),
                                      criterion)
                 .set_optim_method(optim.SGD(
                     learning_rate=args.learning_rate, momentum=0.9))
                 .set_end_when(optim.max_epoch(args.max_epoch)))
    trained = optimizer.optimize()

    # checkpoint + reload (Test.scala analog consumes the saved model)
    ckpt_dir = args.checkpoint or tempfile.mkdtemp(prefix="lenet_local_")
    path = os.path.join(ckpt_dir, "lenet.bigdl")
    save_bigdl_module(trained, path)
    reloaded = load_bigdl_module(path)
    reloaded.evaluate()

    ev = Evaluator(reloaded, params=reloaded._params,
                   state=reloaded._state)
    results = ev.evaluate(pipeline(vimgs, vlbls, False),
                          [optim.Top1Accuracy()])
    acc = results["Top1Accuracy"].result

    # Predict.scala analog: per-sample class predictions
    pred = Predictor(reloaded, params=reloaded._params,
                     state=reloaded._state, batch_size=args.batch_size)
    x = ((vimgs[:8].reshape(-1, 1, 28, 28).astype(np.float32))
         - mnist.TRAIN_MEAN) / mnist.TRAIN_STD
    classes = np.argmax(np.asarray(pred.predict(x)), axis=-1)
    print(f"predictions: {classes.tolist()} (truth {vlbls[:8].tolist()})")
    print(f"final: loss={optimizer.state['loss']:.4f} top1={acc:.4f} "
          f"ckpt={path}")


if __name__ == "__main__":
    main()
