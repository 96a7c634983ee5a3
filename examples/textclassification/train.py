"""Text classification with a 1-D CNN over word embeddings.

Mirror of the reference ``DL/example/textclassification/`` (GloVe + news20
→ TemporalConvolution stack).  Without the news20/GloVe downloads it runs
on a deterministic synthetic two-topic corpus; embeddings are learned
(LookupTable) instead of pretrained.
"""

from __future__ import annotations

import argparse

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def synthetic_corpus(n=400, seed=0):
    """Two topics with disjoint preferred vocabularies."""
    import numpy as np
    rng = np.random.default_rng(seed)
    topics = [[f"alpha{i}" for i in range(20)],
              [f"beta{i}" for i in range(20)]]
    shared = [f"w{i}" for i in range(20)]
    texts, labels = [], []
    for _ in range(n):
        y = int(rng.integers(0, 2))
        words = rng.choice(topics[y] + shared, size=12)
        texts.append(" ".join(words))
        labels.append(y)
    return texts, labels


def main():
    p = argparse.ArgumentParser()
    p.add_argument("-b", "--batch-size", type=int, default=32)
    p.add_argument("-e", "--max-epoch", type=int, default=6)
    p.add_argument("--seq-len", type=int, default=12)
    p.add_argument("--embed-dim", type=int, default=32)
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
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch, text
    from bigdl_tpu.dataset.sample import Sample

    texts, labels = synthetic_corpus()
    toks = [text.sentence_tokenizer(t) for t in texts]
    d = text.Dictionary(toks)
    samples = []
    for t, y in zip(toks, labels):
        ids = d.encode(t)[: args.seq_len]
        if len(ids) < args.seq_len:
            ids = np.pad(ids, (0, args.seq_len - len(ids)))
        samples.append(Sample(ids.astype(np.int32), np.int32(y)))

    # embed → temporal conv → max-over-time → classify (the reference's
    # CNN text classifier shape)
    model = (nn.Sequential(name="TextCNN")
             .add(nn.LookupTable(d.vocab_size(), args.embed_dim))
             .add(nn.TemporalConvolution(args.embed_dim, 64, 3))
             .add(nn.ReLU())
             .add(nn.Lambda(lambda x: x.max(axis=1)))
             .add(nn.Linear(64, 2))
             .add(nn.LogSoftMax()))

    train_set = DataSet.array(samples) >> SampleToMiniBatch(args.batch_size)
    opt = (optim.LocalOptimizer(model, train_set, nn.ClassNLLCriterion())
           .set_optim_method(optim.Adam(learning_rate=0.01))
           .set_end_when(optim.max_epoch(args.max_epoch)))
    opt.optimize()

    model.training = False
    xs = np.stack([s.feature for s in samples])
    ys = np.asarray(labels)
    acc = (np.argmax(np.asarray(model.forward(xs)), -1) == ys).mean()
    print(f"final: loss={opt.state['loss']:.4f} train_acc={acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
