"""LeNet-5 training example — the TPU-native mirror of the reference's
``DL/models/lenet/Train.scala:35-101`` (the canonical BigDL entry script).

Usage:
    python examples/lenet/train.py [-f MNIST_DIR] [-b BATCH] [-e EPOCHS]
        [--distributed] [--checkpoint DIR] [--summary DIR] [--cpu]

Without ``-f`` (no MNIST idx files), trains on the deterministic synthetic
MNIST-shaped dataset so the example runs anywhere.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

try:
    import bigdl_tpu  # noqa: F401  (installed via `pip install -e .`)
except ImportError:  # running straight from a repo checkout
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser(description="Train LeNet5 on MNIST")
    p.add_argument("-f", "--folder", default=None,
                   help="MNIST idx files dir (default: synthetic data)")
    p.add_argument("-b", "--batch-size", type=int, default=128)
    p.add_argument("-e", "--max-epoch", type=int, default=5)
    p.add_argument("--learning-rate", type=float, default=0.05)
    p.add_argument("--learning-rate-decay", type=float, default=0.0)
    p.add_argument("--distributed", action="store_true",
                   help="use DistriOptimizer over the device mesh")
    p.add_argument("--checkpoint", default=None, help="checkpoint dir")
    p.add_argument("--summary", default=None, help="tensorboard log dir")
    p.add_argument("--cpu", action="store_true", help="force CPU platform")
    p.add_argument("--synthetic-n", type=int, default=4096)
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset import image, mnist
    from bigdl_tpu.models.lenet import lenet5
    from bigdl_tpu.utils.summary import TrainSummary, ValidationSummary

    if args.folder:
        train_imgs, train_lbls = mnist.load_mnist(args.folder, train=True)
        val_imgs, val_lbls = mnist.load_mnist(args.folder, train=False)
    else:
        train_imgs, train_lbls = mnist.synthetic_mnist(args.synthetic_n)
        val_imgs, val_lbls = mnist.synthetic_mnist(
            args.synthetic_n // 4, seed=99)

    def pipeline(imgs, lbls, mean, std, train=True):
        # validation keeps the ragged final batch (drop_remainder=False)
        # so every sample is scored
        return (DataSet.array(mnist.to_samples(imgs, lbls))
                >> image.BytesToGreyImg()
                >> image.GreyImgNormalizer(mean, std)
                >> SampleToMiniBatch(args.batch_size,
                                     drop_remainder=train))

    train_set = pipeline(train_imgs, train_lbls,
                         mnist.TRAIN_MEAN, mnist.TRAIN_STD)
    val_set = pipeline(val_imgs, val_lbls, mnist.TEST_MEAN, mnist.TEST_STD,
                       train=False)

    model = lenet5(class_num=10)
    cls = optim.DistriOptimizer if args.distributed else optim.LocalOptimizer
    optimizer = (cls(model, train_set, nn.ClassNLLCriterion())
                 .set_optim_method(optim.SGD(
                     learning_rate=args.learning_rate,
                     learning_rate_decay=args.learning_rate_decay,
                     momentum=0.9))
                 .set_end_when(optim.max_epoch(args.max_epoch))
                 .set_validation(optim.every_epoch(), val_set,
                                 [optim.Top1Accuracy(),
                                  optim.Top5Accuracy()]))
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, optim.every_epoch())
    if args.summary:
        optimizer.set_train_summary(TrainSummary(args.summary, "lenet"))
        optimizer.set_val_summary(ValidationSummary(args.summary, "lenet"))

    optimizer.optimize()
    print(f"final: epoch={optimizer.state['epoch']} "
          f"loss={optimizer.state['loss']:.4f} "
          f"val_top1={optimizer.state.get('score', float('nan')):.4f}")


if __name__ == "__main__":
    main()
