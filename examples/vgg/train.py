"""VGG-16 CIFAR-10 training recipe.

Mirror of the reference ``DL/models/vgg/Train.scala``: VggForCifar10,
SGD lr 0.01 / weight-decay 5e-4 / momentum 0.9 with EpochStep(25, /2)
(the reference's "regime" schedule), normalize + flip/crop augmentation.
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
    p = argparse.ArgumentParser(description="Train VGG on CIFAR-10")
    p.add_argument("-f", "--folder", default=None)
    p.add_argument("-b", "--batch-size", type=int, default=128)
    p.add_argument("-e", "--max-epoch", type=int, default=90)
    p.add_argument("--learning-rate", type=float, default=0.01)
    p.add_argument("--distributed", action="store_true")
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--synthetic-n", type=int, default=1024)
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import (DataSet, MTSampleToMiniBatch,
                                   SampleToMiniBatch, cifar, image)
    from bigdl_tpu.models.vgg import vgg_for_cifar10

    if args.folder:
        tr_i, tr_l = cifar.load_cifar10(args.folder, train=True)
        te_i, te_l = cifar.load_cifar10(args.folder, train=False)
    else:
        tr_i, tr_l = cifar.synthetic_cifar(args.synthetic_n)
        te_i, te_l = cifar.synthetic_cifar(args.synthetic_n // 4, seed=9)

    norm = image.BGRImgNormalizer(cifar.TRAIN_MEAN, cifar.TRAIN_STD)
    # constructed ONCE: fresh per-sample instances would replay the same
    # "random" crop/flip draw for every sample (rng state lives in them)
    train_aug = (norm, image.RandomCropper(32, 32, pad=4), image.HFlip(),
                 image.ChannelOrder("CHW"))

    def augment(s):
        for t in train_aug:
            s = next(iter(t(iter([s]))))
        return s

    train_set = (DataSet.array(cifar.to_samples(tr_i, tr_l),
                               distributed=args.distributed)
                 >> MTSampleToMiniBatch(args.batch_size, augment, workers=8))
    val_set = (DataSet.array(cifar.to_samples(te_i, te_l))
               >> norm >> image.ChannelOrder("CHW")
               >> SampleToMiniBatch(args.batch_size, drop_remainder=False))

    model = vgg_for_cifar10(class_num=10)
    sgd = optim.SGD(learning_rate=args.learning_rate, momentum=0.9,
                    dampening=0.0, weight_decay=5e-4,
                    learning_rate_schedule=optim.EpochStep(25, 0.5))
    cls = optim.DistriOptimizer if args.distributed else optim.LocalOptimizer
    optimizer = (cls(model, train_set, nn.ClassNLLCriterion())
                 .set_optim_method(sgd)
                 .set_end_when(optim.max_epoch(args.max_epoch))
                 .set_validation(optim.every_epoch(), val_set,
                                 [optim.Top1Accuracy()]))
    optimizer.optimize()
    print(f"final: epoch={optimizer.state['epoch']} "
          f"loss={optimizer.state['loss']:.4f} "
          f"val_top1={optimizer.state.get('score', float('nan')):.4f}")
    return optimizer


if __name__ == "__main__":
    main()
