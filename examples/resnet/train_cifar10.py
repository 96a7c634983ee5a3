"""ResNet CIFAR-10 training recipe.

Mirror of the reference ``DL/models/resnet/TrainCIFAR10.scala``: ResNet-20
(6n+2), SGD momentum 0.9 / weight-decay 1e-4 / nesterov, LR 0.1 with the
multistep /10 at epochs 80 and 120 (165 epochs total), pad-4 random crop
32x32 + horizontal flip + per-channel normalization augmentation.

Runs on real CIFAR-10 (``-f`` pointing at cifar-10-batches-{bin,py}) or a
deterministic synthetic stand-in so the script works anywhere.
"""

from __future__ import annotations

import argparse

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser(description="Train ResNet on CIFAR-10")
    p.add_argument("-f", "--folder", default=None,
                   help="CIFAR-10 dir (default: synthetic data)")
    p.add_argument("--depth", type=int, default=20)
    p.add_argument("-b", "--batch-size", type=int, default=128)
    p.add_argument("-e", "--max-epoch", type=int, default=165)
    p.add_argument("--learning-rate", type=float, default=0.1)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--distributed", action="store_true")
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--synthetic-n", type=int, default=2048)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--summary", default=None)
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import logging
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import (DataSet, MTSampleToMiniBatch,
                                   SampleToMiniBatch, cifar, image)
    from bigdl_tpu.models.resnet import resnet_cifar

    if args.folder:
        tr_i, tr_l = cifar.load_cifar10(args.folder, train=True)
        te_i, te_l = cifar.load_cifar10(args.folder, train=False)
    else:
        tr_i, tr_l = cifar.synthetic_cifar(args.synthetic_n)
        te_i, te_l = cifar.synthetic_cifar(args.synthetic_n // 4, seed=9)

    norm = image.BGRImgNormalizer(cifar.TRAIN_MEAN, cifar.TRAIN_STD)
    # constructed ONCE: the transforms carry (thread-safe) rng state, so a
    # fresh instance per sample would replay the same "random" draw forever
    train_aug = (norm, image.RandomCropper(32, 32, pad=4), image.HFlip(),
                 image.ChannelOrder("CHW"))

    def augment(s):
        # reference recipe: pad 4 + random crop 32 + random hflip (train)
        for t in train_aug:
            s = next(iter(t(iter([s]))))
        return s

    train_set = (DataSet.array(cifar.to_samples(tr_i, tr_l),
                               distributed=args.distributed)
                 >> MTSampleToMiniBatch(args.batch_size, augment, workers=8))
    val_set = (DataSet.array(cifar.to_samples(te_i, te_l))
               >> image.BGRImgNormalizer(cifar.TRAIN_MEAN, cifar.TRAIN_STD)
               >> image.ChannelOrder("CHW")
               >> SampleToMiniBatch(args.batch_size, drop_remainder=False))

    model = resnet_cifar(depth=args.depth, class_num=10)
    sgd = optim.SGD(
        learning_rate=args.learning_rate, momentum=0.9, dampening=0.0,
        nesterov=True, weight_decay=args.weight_decay,
        learning_rate_schedule=optim.MultiStep([80, 120], 0.1,
                                               epoch_based=True))
    cls = optim.DistriOptimizer if args.distributed else optim.LocalOptimizer
    optimizer = (cls(model, train_set, nn.ClassNLLCriterion())
                 .set_optim_method(sgd)
                 .set_end_when(optim.max_epoch(args.max_epoch))
                 .set_validation(optim.every_epoch(), val_set,
                                 [optim.Top1Accuracy()]))
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, optim.every_epoch())
    if args.summary:
        from bigdl_tpu.utils.summary import TrainSummary, ValidationSummary
        optimizer.set_train_summary(TrainSummary(args.summary, "resnet"))
        optimizer.set_val_summary(ValidationSummary(args.summary, "resnet"))
    optimizer.optimize()
    print(f"final: epoch={optimizer.state['epoch']} "
          f"loss={optimizer.state['loss']:.4f} "
          f"val_top1={optimizer.state.get('score', float('nan')):.4f}")
    return optimizer


if __name__ == "__main__":
    main()
