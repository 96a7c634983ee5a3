"""Image-folder classification through the vision-2.0 pipeline.

Mirror of the reference ``DL/example/imageclassification/ImagePredictor``
(+ ``MlUtils``): read images, run the ImageFrame feature pipeline
(resize → center crop → channel normalize), batch, and predict with a
classifier — the inference-side twin of the Inception training recipe.

With ``--folder`` pointing at JPEG/PNG files it classifies those;
without, it generates a synthetic image set so the example runs
anywhere.
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
    p = argparse.ArgumentParser(description="Classify an image folder")
    p.add_argument("--folder", default=None,
                   help="dir of images (default: synthetic)")
    p.add_argument("--model", default=None,
                   help=".bigdl classifier (default: fresh Inception-v1 "
                        "head on 8 classes)")
    p.add_argument("--classes", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--topn", type=int, default=3)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:  # compiles for the chip are worth keeping
        from bigdl_tpu.engine import Engine
        Engine.enable_compile_cache()

    import numpy as np
    from bigdl_tpu.transform.vision import (AspectScale, CenterCrop,
                                            ChannelNormalize, ImageFeature,
                                            ImageFrameToSample,
                                            LocalImageFrame, MatToFloats)
    from bigdl_tpu.optim.predictor import Predictor
    from bigdl_tpu.interop import load_bigdl_module
    from bigdl_tpu.models.inception import inception_v1

    rng = np.random.default_rng(0)
    if args.folder:
        from PIL import Image
        names, mats = [], []
        for fn in sorted(os.listdir(args.folder)):
            if fn.lower().endswith((".jpg", ".jpeg", ".png")):
                img = Image.open(os.path.join(args.folder, fn))
                mats.append(np.asarray(img.convert("RGB"), np.float32))
                names.append(fn)
    else:
        names = [f"synthetic_{i}.jpg" for i in range(16)]
        mats = [rng.integers(0, 255, (280, 320, 3)).astype(np.float32)
                for _ in names]

    frame = LocalImageFrame([ImageFeature(image=m, uri=n)
                             for m, n in zip(mats, names)])
    frame = (frame
             >> AspectScale(256)
             >> CenterCrop(224, 224)
             >> ChannelNormalize((123.0, 117.0, 104.0),
                                 (58.4, 57.1, 57.4))
             >> MatToFloats()
             >> ImageFrameToSample(to_chw=True))
    batch = np.stack([f["sample"].feature for f in frame.features])

    if args.model:
        model = load_bigdl_module(args.model)
    else:
        model = inception_v1(class_num=args.classes)
        model.initialize(0)
    model.evaluate()
    pred = Predictor(model, params=model._params, state=model._state,
                     batch_size=args.batch_size)
    probs = np.exp(np.asarray(pred.predict(batch)))  # model ends LogSoftMax
    top = np.argsort(-probs, axis=1)[:, :args.topn]
    for n, t, pr in zip(names, top, probs):
        pairs = ", ".join(f"cls{c}:{pr[c]:.3f}" for c in t)
        print(f"{n}: {pairs}")
    print(f"final: predicted={len(names)} classes={probs.shape[1]}")


if __name__ == "__main__":
    main()
