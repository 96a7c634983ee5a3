"""PTB word-level language model (LSTM).

Mirror of the reference ``DL/example/languagemodel/{PTBModel,PTBWordLM}``:
tokenize a corpus into word ids, batch into (seq, next-word-seq) windows,
train the embed→LSTM×2→linear model (``models/rnn.ptb_model``), report
perplexity.

With ``-f`` pointing at ``ptb.train.txt`` it uses real PTB; without, a
deterministic synthetic Zipf corpus stands in so the example runs
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
    p = argparse.ArgumentParser(description="PTB LSTM language model")
    p.add_argument("-f", "--data", default=None,
                   help="ptb.train.txt path (default: synthetic corpus)")
    p.add_argument("-b", "--batch-size", type=int, default=20)
    p.add_argument("-e", "--max-epoch", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=20)
    p.add_argument("--vocab", type=int, default=200)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--scan-unroll", type=int, default=1,
                   help="unroll the time loop (exact math; speeds up "
                        "small-batch RNNs on TPU, see PERF.md)")
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
    from bigdl_tpu.dataset.text import Dictionary
    from bigdl_tpu.models.rnn import ptb_model

    if args.data:
        words = open(args.data).read().replace("\n", " <eos> ").split()
    else:
        rng = np.random.default_rng(0)
        zipf = rng.zipf(1.4, size=40000)
        words = [f"w{min(int(z), args.vocab - 2)}" for z in zipf]

    dictionary = Dictionary([words], vocab_size=args.vocab)
    ids = np.asarray([dictionary.index(w) for w in words], np.int32)

    T = args.seq_len
    n_win = (len(ids) - 1) // T
    xs = ids[:n_win * T].reshape(n_win, T)
    ys = ids[1:n_win * T + 1].reshape(n_win, T)
    samples = [Sample(x, y) for x, y in zip(xs, ys)]
    ds = DataSet.array(samples) >> SampleToMiniBatch(args.batch_size)

    vocab = dictionary.vocab_size()
    model = ptb_model(vocab_size=vocab, embed_dim=args.hidden,
                      hidden_size=args.hidden, num_layers=args.layers,
                      scan_unroll=args.scan_unroll)
    criterion = nn.TimeDistributedCriterion(
        nn.CrossEntropyCriterion(), size_average=True)
    optimizer = (optim.LocalOptimizer(model, ds, criterion)
                 .set_optim_method(optim.Adam(learning_rate=0.01))
                 # LSTM steps at this size are a few ms — host dispatch
                 # is the bottleneck; K=8 is what the benchmark's PTB
                 # cell runs too (PERF.md section 4)
                 .set_steps_per_dispatch(8)
                 .set_end_when(optim.max_epoch(args.max_epoch)))
    optimizer.optimize()
    loss = optimizer.state["loss"]
    ppl = float(np.exp(min(loss, 20.0)))
    print(f"final: loss={loss:.4f} perplexity={ppl:.1f} vocab={vocab}")


if __name__ == "__main__":
    main()
