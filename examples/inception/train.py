"""Inception-v1 ImageNet training recipe.

Mirror of the reference ``DL/models/inception/Train.scala`` +
``Options.scala``: Inception-v1, SGD momentum 0.9 / weight-decay 1e-4,
poly(0.5) LR decay over ``max_iteration`` (the reference's default
recipe), warmup supported via ``--warmup-epochs`` (Warmup →
SequentialSchedule, as the distributed recipe uses), Inception-style
random-alter-aspect crop + flip augmentation.

Without a real ImageNet tree it trains on a synthetic 224x224 dataset so
the script runs anywhere (the reference needs its seq-file pipeline).
"""

from __future__ import annotations

import argparse

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def synthetic_imagenet(n, size=224, classes=1000, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, n).astype(np.int32)
    imgs = rng.integers(0, 60, (n, size, size, 3)).astype(np.float32)
    for i, y in enumerate(labels):
        r, c = divmod(int(y) % 16, 4)
        imgs[i, r * 56:(r + 1) * 56, c * 56:(c + 1) * 56, int(y) % 3] += 150
    return imgs, labels


def main():
    p = argparse.ArgumentParser(description="Train Inception-v1")
    p.add_argument("-b", "--batch-size", type=int, default=32)
    p.add_argument("--max-iteration", type=int, default=62000)
    p.add_argument("-e", "--max-epoch", type=int, default=None)
    p.add_argument("--learning-rate", type=float, default=0.0898)
    p.add_argument("--warmup-epochs", type=int, default=0)
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--distributed", action="store_true")
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--synthetic-n", type=int, default=256)
    p.add_argument("--image-size", type=int, default=224)
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import DataSet, MTSampleToMiniBatch, cifar
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.inception import inception_v1
    from bigdl_tpu.transform import vision as V

    imgs, labels = synthetic_imagenet(args.synthetic_n, args.image_size,
                                      args.classes)
    samples = cifar.to_samples(imgs.astype("uint8"), labels)

    aug = (V.RandomAlterAspect(target_size=args.image_size)
           >> V.HFlip()
           >> V.ChannelNormalize((123.0, 117.0, 104.0), (58.4, 57.1, 57.4))
           >> V.ImageFrameToSample())

    def augment(s):
        f = V.ImageFeature(s.feature, s.label)
        return aug(f)["sample"]

    train_set = (DataSet.array(samples, distributed=args.distributed)
                 >> MTSampleToMiniBatch(args.batch_size, augment, workers=8))

    schedule = optim.Poly(0.5, args.max_iteration)
    if args.warmup_epochs:
        iters_per_epoch = max(1, len(samples) // args.batch_size)
        warm = args.warmup_epochs * iters_per_epoch
        delta = args.learning_rate / max(warm, 1)
        seq = optim.SequentialSchedule()
        seq.add(optim.Warmup(delta, warm), warm)
        seq.add(optim.Poly(0.5, args.max_iteration))
        schedule = seq
    sgd = optim.SGD(learning_rate=args.learning_rate, momentum=0.9,
                    dampening=0.0, weight_decay=1e-4,
                    learning_rate_schedule=schedule)

    end = (optim.max_epoch(args.max_epoch) if args.max_epoch
           else optim.max_iteration(args.max_iteration))
    model = inception_v1(class_num=args.classes)
    cls = optim.DistriOptimizer if args.distributed else optim.LocalOptimizer
    optimizer = (cls(model, train_set, nn.ClassNLLCriterion())
                 .set_optim_method(sgd)
                 .set_end_when(end))
    optimizer.optimize()
    print(f"final: epoch={optimizer.state['epoch']} "
          f"loss={optimizer.state['loss']:.4f}")
    return optimizer


if __name__ == "__main__":
    main()
