"""Builder for ``ptb-medium-lstm``: the sizes of the configuration file →
the product's model, criterion and synthetic data."""

from __future__ import annotations

import numpy as np


def _model(cfg: dict, kernel_impl):
    from bigdl_tpu.models.rnn import ptb_model
    m = cfg["model"]
    return ptb_model(m["vocab_size"], m["embed_size"], m["hidden_size"],
                     m["num_layers"], kernel_impl=kernel_impl)


def build_model(cfg: dict):
    return _model(cfg, None)  # the default kernel_impl: what a user gets


def reference_model(cfg: dict, model):
    """The same network with every LSTM cell as XLA's plain chain, so the
    fused kernel is checked too.  It takes the same parameter tree."""
    return _model(cfg, "xla")


def criterion(cfg: dict):
    from bigdl_tpu import nn
    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion())


def make_samples(cfg: dict, seed: int, global_batch: int, n_batches: int):
    from bigdl_tpu.dataset import Sample
    m = cfg["model"]
    rng = np.random.default_rng(seed)
    n = global_batch * n_batches
    tokens = rng.integers(0, m["vocab_size"],
                          (2, n, m["num_steps"])).astype(np.int32)
    return [Sample(tokens[0, i], tokens[1, i]) for i in range(n)]


def train_flops_per_record(cfg: dict) -> float:
    """Forward + backward of one sequence: per token, each LSTM layer is a
    (in + hidden) x 4*hidden product and the output layer a hidden x
    vocabulary one; 2 operations a multiply-accumulate, backward twice
    the forward.  The embedding is a lookup and counts nothing.  Counted
    from the sizes because XLA's cost analysis counts a scan body once,
    and the 35 steps are a scan."""
    m = cfg["model"]
    h = m["hidden_size"]
    macs = 0
    for layer in range(m["num_layers"]):
        in_size = m["embed_size"] if layer == 0 else h
        macs += (in_size + h) * 4 * h
    macs += h * m["vocab_size"]
    return 3.0 * 2.0 * macs * m["num_steps"]
