"""ResNet-50 ImageNet training recipe.

Mirror of the reference ``DL/models/resnet/TrainImageNet.scala`` +
``README.md:131-149`` large-batch recipe: batch 8192, 90 epochs, 5-epoch
linear warmup to maxLr 3.2, then /10 at epochs 30/60/80, SGD momentum 0.9
weight-decay 1e-4, label-smoothing-free NLL.  Input pipeline:
random-alter-aspect crop + flip + channel normalization (the reference's
seq-file ImageNet path; Hadoop SequenceFiles via ``--seqfiles`` glob or a
synthetic stand-in anywhere).
"""

from __future__ import annotations

import argparse
import glob as globmod

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser(description="Train ResNet-50 on ImageNet")
    p.add_argument("--seqfiles", default=None,
                   help="glob of Hadoop SequenceFiles holding raw "
                        "HWC uint8 images (reference seq-file pipeline)")
    p.add_argument("-b", "--batch-size", type=int, default=256,
                   help="global batch (reference recipe: 8192 across "
                        "the cluster)")
    p.add_argument("-e", "--max-epoch", type=int, default=90)
    p.add_argument("--max-lr", type=float, default=3.2,
                   help="post-warmup LR for the batch-8192 recipe; "
                        "scale linearly with batch")
    p.add_argument("--warmup-epochs", type=int, default=5)
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--depth", type=int, default=50, choices=[50])
    p.add_argument("--distributed", action="store_true")
    p.add_argument("--nhwc", action="store_true",
                   help="TPU-preferred layout")
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--synthetic-n", type=int, default=512)
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np

    from bigdl_tpu import nn, optim
    from bigdl_tpu.dataset import (DataSet, MTSampleToMiniBatch, seqfile)
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.resnet import resnet50
    from bigdl_tpu.transform import vision as V

    size = args.image_size
    # Samples hold uint8 HWC images; augmentation converts to float per
    # batch.  Keeping the set in host memory mirrors the reference's
    # CachedDistriDataSet (the whole dataset cached across cluster RAM,
    # divided per host by DistributedDataSet sharding).
    samples = []
    if args.seqfiles:
        paths = sorted(globmod.glob(args.seqfiles))
        for label, blob in seqfile.seqfiles_to_byte_records(paths):
            img = np.frombuffer(blob, np.uint8)
            side = int(round((img.size / 3) ** 0.5))
            if side * side * 3 != img.size:
                raise ValueError(
                    f"seqfile record of {img.size} bytes is not a square "
                    "raw-HWC image; pre-resize to a fixed square (the "
                    "raw format carries no dimension header)")
            # reference seqfile labels are 1-based (Torch convention);
            # this framework's criterions are 0-based
            samples.append(Sample(img.reshape(side, side, 3),
                                  np.int32(label - 1)))
    else:
        rng = np.random.default_rng(0)
        labels = rng.integers(0, args.classes, args.synthetic_n)
        for y in labels:
            img = rng.integers(0, 60, (size, size, 3)).astype(np.uint8)
            r, c = divmod(int(y) % 16, 4)
            img[r * (size // 4):(r + 1) * (size // 4),
                c * (size // 4):(c + 1) * (size // 4), int(y) % 3] += 150
            samples.append(Sample(img, np.int32(y)))

    fmt = "NHWC" if args.nhwc else "NCHW"
    aug = (V.RandomAlterAspect(target_size=size)
           >> V.HFlip()
           >> V.ChannelNormalize((123.68, 116.78, 103.94),
                                 (58.4, 57.1, 57.4))
           >> V.ImageFrameToSample(to_chw=(fmt == "NCHW")))

    def augment(s):
        # ImageFeature casts to float32 itself; no extra copy here
        f = V.ImageFeature(s.feature, s.label)
        return aug(f)["sample"]

    train_set = (DataSet.array(samples, distributed=args.distributed)
                 >> MTSampleToMiniBatch(args.batch_size, augment,
                                        workers=8))

    iters_per_epoch = max(1, len(samples) // args.batch_size)
    warm = args.warmup_epochs * iters_per_epoch
    # linear warmup to max_lr, then /10 at epochs 30/60/80 — exactly the
    # reference recipe's EpochDecayWithWarmUp (README.md:131-149)
    base_lr = args.max_lr / max(warm, 1)
    delta = (args.max_lr - base_lr) / max(warm, 1)

    def decay(epoch):
        return sum(1 for e in (30, 60, 80) if epoch >= e)

    sgd = optim.SGD(learning_rate=base_lr, momentum=0.9, dampening=0.0,
                    weight_decay=1e-4,
                    learning_rate_schedule=optim.EpochDecayWithWarmUp(
                        warm, delta, decay))

    model = resnet50(class_num=args.classes, format=fmt)
    cls = optim.DistriOptimizer if args.distributed else optim.LocalOptimizer
    optimizer = (cls(model, train_set, nn.ClassNLLCriterion())
                 .set_optim_method(sgd)
                 .set_end_when(optim.max_epoch(args.max_epoch)))
    optimizer.optimize()
    print(f"final: epoch={optimizer.state['epoch']} "
          f"loss={optimizer.state['loss']:.4f}")
    return optimizer


if __name__ == "__main__":
    main()
