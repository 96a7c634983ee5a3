"""ML-pipeline-style estimator demos.

Mirror of the reference ``DL/example/MLPipeline/``:
``DLClassifierLogisticRegression`` (2-feature LR via the fit/transform
facade), ``DLClassifierLeNet`` (image classifier through the same
interface), and ``DLEstimatorMultiLabelLR`` (multi-label regression via
the raw NNEstimator).  The DataFrame is replaced by plain arrays — the
estimator facade is the ``DLEstimator``/``DLClassifier`` analog
(SURVEY §2.7).
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
    p.add_argument("-e", "--max-epoch", type=int, default=20)
    args = p.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np
    from bigdl_tpu import nn, optim
    from bigdl_tpu.estimator import NNClassifier, NNEstimator
    from bigdl_tpu.dataset import mnist
    from bigdl_tpu.models.lenet import lenet5

    rng = np.random.RandomState(0)

    # 1) DLClassifierLogisticRegression: y = 1[x0 + x1 > 1]
    x = rng.rand(512, 2).astype(np.float32)
    y = (x.sum(1) > 1.0).astype(np.int32)
    lr_model = nn.Sequential(nn.Linear(2, 2), nn.LogSoftMax())
    clf = NNClassifier(lr_model, batch_size=32, max_epoch=args.max_epoch,
                       optim_method=optim.SGD(learning_rate=0.5))
    lr_acc = (clf.fit(x, y).transform(x) == y).mean()
    print(f"logistic regression train acc: {lr_acc:.4f}")

    # 2) DLClassifierLeNet: the image classifier through fit/transform
    imgs, lbls = mnist.synthetic_mnist(4096)
    xi = ((imgs.reshape(-1, 1, 28, 28).astype(np.float32))
          - mnist.TRAIN_MEAN) / mnist.TRAIN_STD
    lenet_clf = NNClassifier(
        lenet5(class_num=10), batch_size=128, max_epoch=3,
        optim_method=optim.SGD(learning_rate=0.1, momentum=0.9))
    lenet_acc = (lenet_clf.fit(xi, lbls).transform(xi) == lbls).mean()
    print(f"lenet train acc: {lenet_acc:.4f}")

    # 3) DLEstimatorMultiLabelLR: 2-output linear regression on MSE
    xm = rng.rand(256, 2).astype(np.float32)
    w = np.asarray([[2.0, -1.0], [0.5, 1.5]], np.float32)
    ym = xm @ w.T + np.asarray([0.1, -0.2], np.float32)
    est = NNEstimator(nn.Linear(2, 2), nn.MSECriterion(), batch_size=32,
                      max_epoch=args.max_epoch,
                      optim_method=optim.Adam(learning_rate=0.05))
    fitted = est.fit(xm, ym)
    mse = float(((fitted.transform(xm) - ym) ** 2).mean())
    print(f"multi-label LR mse: {mse:.5f}")
    print(f"final: train_acc={lr_acc:.4f} lenet_acc={lenet_acc:.4f} "
          f"mse={mse:.5f}")


if __name__ == "__main__":
    main()
