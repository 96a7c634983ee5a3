"""Image transfer learning: frozen pretrained trunk + trainable head.

Mirror of the reference ``DL/example/dlframes/imageTransferLearning``
(and ``imageInference``): a pretrained conv trunk extracts features
(inference only), a small classifier head trains on top via the
estimator facade — the DataFrame pipeline replaced by plain arrays.
"""

from __future__ import annotations

import argparse
import os
import sys

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true")
    p.add_argument("-e", "--max-epoch", type=int, default=10)
    p.add_argument("-n", "--samples", type=int, default=512)
    args = p.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np
    from bigdl_tpu import nn, optim
    from bigdl_tpu.estimator import NNClassifier
    from bigdl_tpu.optim.predictor import Predictor

    rng = np.random.RandomState(0)

    # "pretrained" trunk (stands in for a loaded zoo model; swap with
    # interop.load_bigdl_module / load_caffe_model for real weights)
    trunk = nn.Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1),
        nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2),
        nn.SpatialConvolution(8, 16, 3, 3, 1, 1, 1, 1),
        nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2),
        nn.Flatten())
    trunk.initialize(7)
    trunk.evaluate()

    # 2-class dataset the trunk was NOT trained on
    n = args.samples
    y = rng.randint(0, 2, n)
    x = rng.rand(n, 3, 16, 16).astype(np.float32)
    x[y == 1, :, 4:12, 4:12] += 0.8  # class-1 images get a bright square

    # inference pass: frozen trunk extracts features (imageInference)
    feats = np.asarray(Predictor(trunk, params=trunk._params,
                                 state=trunk._state,
                                 batch_size=128).predict(x))
    print(f"trunk features: {feats.shape}")

    # trainable head fits on the features (imageTransferLearning)
    head = nn.Sequential(nn.Linear(feats.shape[1], 16), nn.ReLU(),
                         nn.Linear(16, 2), nn.LogSoftMax())
    clf = NNClassifier(head, batch_size=64, max_epoch=args.max_epoch,
                       optim_method=optim.Adam(learning_rate=0.01))
    fitted = clf.fit(feats, y)
    acc = float((fitted.transform(feats) == y).mean())
    print(f"final: train_acc={acc:.4f}")


if __name__ == "__main__":
    main()
