"""MNIST autoencoder training recipe.

Mirror of the reference ``DL/models/autoencoder/Train.scala``: 784→32→784
sigmoid autoencoder trained with MSE against the (normalized) input
itself, Adagrad like the reference's default.
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
    p = argparse.ArgumentParser(description="Train an MNIST autoencoder")
    p.add_argument("-f", "--folder", default=None,
                   help="MNIST idx dir (default: synthetic)")
    p.add_argument("-b", "--batch-size", type=int, default=128)
    p.add_argument("-e", "--max-epoch", type=int, default=5)
    p.add_argument("--bottleneck", type=int, default=32)
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
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch, mnist
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.autoencoder import autoencoder

    if args.folder:
        imgs, _ = mnist.load_mnist(args.folder, train=True)
    else:
        imgs, _ = mnist.synthetic_mnist(args.synthetic_n)
    x = imgs.astype(np.float32) / 255.0  # sigmoid output range
    # target = the input itself (reference feeds the image as label too)
    samples = [Sample(x[i], x[i].reshape(-1)) for i in range(len(x))]

    model = autoencoder(class_num=args.bottleneck)
    opt = (optim.LocalOptimizer(model, DataSet.array(samples)
                                >> SampleToMiniBatch(args.batch_size),
                                nn.MSECriterion())
           .set_optim_method(optim.Adagrad(learning_rate=0.01))
           .set_end_when(optim.max_epoch(args.max_epoch)))
    opt.optimize()
    model.training = False
    recon = np.asarray(model.forward(x[:256]))
    mse = float(np.mean((recon - x[:256].reshape(256, -1)) ** 2))
    print(f"final: epoch={opt.state['epoch']} loss={opt.state['loss']:.5f} "
          f"recon_mse={mse:.5f}")
    return opt


if __name__ == "__main__":
    main()
