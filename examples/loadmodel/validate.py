"""ModelValidator: load a model in ANY supported format and measure
Top-1/Top-5 on a validation set.

Mirror of the reference ``DL/example/loadmodel/ModelValidator.scala``
(``--modelType {bigdl,caffe,torch}`` + AlexNet/Inception validation).
Without ``--model`` it trains a small AlexNet-style net on synthetic
data, exports it to EVERY format, and validates each reload — the full
interop matrix exercised through the evaluator.
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


def alexnet_small(class_num: int = 10):
    """AlexNet-shaped net scaled to 32x32 inputs (the reference
    validates full AlexNet from ``example/loadmodel/AlexNet.scala``)."""
    from bigdl_tpu import nn
    return nn.Sequential(
        nn.SpatialConvolution(3, 16, 3, 3, 1, 1, 1, 1, name="conv1"),
        nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2, ceil_mode=True),
        nn.SpatialCrossMapLRN(5, 1e-4, 0.75, name="lrn1"),
        nn.SpatialConvolution(16, 32, 3, 3, 1, 1, 1, 1, name="conv2"),
        nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2, ceil_mode=True),
        nn.Flatten(),
        nn.Linear(32 * 8 * 8, 64, name="fc1"),
        nn.ReLU(),
        nn.Linear(64, class_num, name="fc2"),
        nn.SoftMax(),
        name="AlexNetSmall")


def main():
    p = argparse.ArgumentParser(description="Validate a saved model")
    p.add_argument("--model", default=None, help="model file to validate")
    p.add_argument("--model-type", default="bigdl",
                   choices=["bigdl", "caffe", "torch"],
                   help="format of --model (reference modelType flag)")
    p.add_argument("--prototxt", default=None,
                   help="net definition (caffe models)")
    p.add_argument("-b", "--batch-size", type=int, default=64)
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
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.interop import (load_bigdl_module, load_caffe_model,
                                   load_torch_module, save_bigdl_module,
                                   save_caffe, save_torch_module)
    from bigdl_tpu.optim.predictor import Evaluator

    rng = np.random.RandomState(0)
    n, classes = 512, 10
    centers = rng.randn(classes, 3, 1, 1).astype(np.float32) * 2
    yv = rng.randint(0, classes, n)
    xv = (centers[yv] + rng.randn(n, 3, 32, 32).astype(np.float32) * 0.5)
    val_set = (DataSet.array([Sample(x, np.int32(t))
                              for x, t in zip(xv, yv)])
               >> SampleToMiniBatch(args.batch_size,
                                    drop_remainder=False))

    def validate(model, tag):
        model.evaluate()
        ev = Evaluator(model, params=model._params, state=model._state)
        r = ev.evaluate(val_set, [optim.Top1Accuracy(),
                                  optim.Top5Accuracy()])
        t1 = r["Top1Accuracy"].result
        t5 = r["Top5Accuracy"].result
        print(f"{tag}: top1={t1:.4f} top5={t5:.4f}")
        return t1

    loaders = {
        "bigdl": lambda path: load_bigdl_module(path),
        "torch": lambda path: load_torch_module(path),
        "caffe": lambda path: load_caffe_model(args.prototxt, path),
    }

    if args.model:
        t1 = validate(loaders[args.model_type](args.model),
                      args.model_type)
        print(f"final: top1={t1:.4f}")
        return

    # no model given: train briefly, export to every format, validate all
    import jax
    import jax.numpy as jnp
    model = alexnet_small(classes)
    model.initialize(0)
    crit = nn.CategoricalCrossEntropy()

    def loss_fn(params, x, y):
        out, _ = model.apply(params, model._state, x, training=False)
        return crit.apply(out, y)

    step = jax.jit(jax.value_and_grad(loss_fn))
    params = model._params
    for i in range(40):
        ix = rng.choice(n, 64, replace=False)
        l, g = step(params, jnp.asarray(xv[ix]), jnp.asarray(yv[ix]))
        params = jax.tree_util.tree_map(lambda a, b: a - 0.05 * b,
                                        params, g)
    model._params = params

    tmp = tempfile.mkdtemp(prefix="validator_")
    b_path = os.path.join(tmp, "m.bigdl")
    t_path = os.path.join(tmp, "m.t7")
    c_proto = os.path.join(tmp, "m.prototxt")
    c_path = os.path.join(tmp, "m.caffemodel")
    save_bigdl_module(model, b_path)
    save_torch_module(model, t_path)
    save_caffe(model, c_proto, c_path, input_shapes=[[1, 3, 32, 32]])
    args.prototxt = c_proto

    base = validate(model, "in-memory")
    accs = [validate(loaders["bigdl"](b_path), "bigdl"),
            validate(loaders["torch"](t_path), "torch"),
            validate(loaders["caffe"](c_path), "caffe")]
    assert all(abs(a - base) < 1e-6 for a in accs), \
        "reloaded models diverge from the trained one"
    print(f"final: top1={base:.4f} formats=bigdl,torch,caffe")


if __name__ == "__main__":
    main()
