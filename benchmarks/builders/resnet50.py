"""Builder for ``resnet50-imagenet``: the sizes of the configuration file
→ the product's model, criterion and synthetic data."""

from __future__ import annotations

import numpy as np


def _check_sizes(m: dict) -> None:
    """``bigdl_tpu.models.resnet.resnet50`` fixes its widths in code; the
    file must state the same ones or the cell would not be what it says."""
    want = {"stem_width": 64, "stage_blocks": [3, 4, 6, 3],
            "bottleneck_widths": [64, 128, 256, 512],
            "bottleneck_expansion": 4, "image_channels": 3,
            "format": "NHWC"}
    for k, v in want.items():
        if m[k] != v:
            raise ValueError(f"resnet50 builds {k}={v}, the file says "
                             f"{m[k]}")


def build_model(cfg: dict):
    from bigdl_tpu.models.resnet import resnet50
    m = cfg["model"]
    _check_sizes(m)
    return resnet50(class_num=m["num_classes"], format=m["format"])


def reference_model(cfg: dict, model):
    return model  # no kernel in it: the same plain network


def criterion(cfg: dict):
    from bigdl_tpu import nn
    return nn.ClassNLLCriterion()


def make_samples(cfg: dict, seed: int, global_batch: int, n_batches: int):
    """One global batch of noise, re-labelled for each batch of the
    epoch: the Samples of different batches share their image arrays."""
    from bigdl_tpu.dataset import Sample
    m = cfg["model"]
    rng = np.random.default_rng(seed)
    side, ch = m["image_size"], m["image_channels"]
    images = rng.standard_normal((global_batch, side, side, ch),
                                 dtype=np.float32)
    labels = rng.integers(0, m["num_classes"],
                          (n_batches, global_batch)).astype(np.int32)
    return [Sample(images[i], labels[b, i])
            for b in range(n_batches) for i in range(global_batch)]


def conv_macs(cfg: dict) -> int:
    """Multiply-accumulates of one forward pass of one image, from the
    sizes: stem 7x7/2, 3x3/2 max-pool, bottlenecks (1x1, 3x3 carrying
    the stride, 1x1, and a 1x1 projection where the shape changes),
    7x7 average pool, the classifier."""
    m = cfg["model"]
    side = m["image_size"] // 2                       # stem stride 2
    macs = side * side * 7 * 7 * m["image_channels"] * m["stem_width"]
    side //= 2                                        # max-pool
    in_c = m["stem_width"]
    for si, (mid, blocks) in enumerate(zip(m["bottleneck_widths"],
                                           m["stage_blocks"])):
        out_c = mid * m["bottleneck_expansion"]
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            out_side = side // stride
            macs += side * side * in_c * mid              # 1x1 a
            macs += out_side * out_side * 9 * mid * mid   # 3x3 b, strided
            macs += out_side * out_side * mid * out_c     # 1x1 c
            if stride != 1 or in_c != out_c:
                macs += out_side * out_side * in_c * out_c  # projection
            side, in_c = out_side, out_c
    return macs + in_c * m["num_classes"]


def train_flops_per_record(cfg: dict) -> float:
    """Forward + backward (twice the forward: gradients with respect to
    the inputs and to the weights), 2 operations a multiply-accumulate.
    The convolutions and the classifier only; BatchNorm, ReLU, pooling
    and the update are left out, as the published 4.09 GMAC leaves them
    out."""
    return 3.0 * 2.0 * conv_macs(cfg)
