"""RNN language-model training recipe.

Mirror of the reference ``DL/models/rnn/Train.scala`` (simple RNN on a
tokenized corpus via Dictionary/TextToLabeledSentence) and
``DL/example/languagemodel/PTBWordLM.scala`` (PTB LSTM with
TimeDistributedCriterion).  Feeds PTB files when ``-f`` points at
``ptb.train.txt``/``ptb.valid.txt``; otherwise a synthetic Zipf corpus.
"""

from __future__ import annotations

import argparse
import os

try:
    import bigdl_tpu  # noqa: F401
except ImportError:
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    p = argparse.ArgumentParser(description="Train an RNN LM")
    p.add_argument("-f", "--folder", default=None,
                   help="dir with ptb.train.txt / ptb.valid.txt")
    p.add_argument("--model", choices=["ptb", "simple"], default="ptb")
    p.add_argument("-b", "--batch-size", type=int, default=20)
    p.add_argument("-e", "--max-epoch", type=int, default=4)
    p.add_argument("--num-steps", type=int, default=20)
    p.add_argument("--vocab-size", type=int, default=10000)
    p.add_argument("--hidden-size", type=int, default=200)
    p.add_argument("--learning-rate", type=float, default=0.005)
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
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch, text
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.rnn import ptb_model, simple_rnn

    if args.folder:
        words = text.read_ptb_words(os.path.join(args.folder,
                                                 "ptb.train.txt"))
        sents = [words]
    else:
        corpus = text.synthetic_corpus(400)
        sents = [text.sentence_tokenizer(s) for s in corpus]
        words = [w for s in sents for w in s]

    d = text.Dictionary([words], vocab_size=args.vocab_size)
    ids = d.encode(words)
    x, y = text.ptb_batches(ids, args.num_steps)
    samples = [Sample(x[i], y[i]) for i in range(len(x))]
    train_set = (DataSet.array(samples)
                 >> SampleToMiniBatch(args.batch_size))

    vocab = d.vocab_size()
    if args.model == "ptb":
        model = ptb_model(vocab_size=vocab, embed_dim=args.hidden_size,
                          hidden_size=args.hidden_size,
                          scan_unroll=args.scan_unroll)
    else:
        model = simple_rnn(input_size=vocab, hidden_size=args.hidden_size,
                           output_size=vocab,
                           scan_unroll=args.scan_unroll)

    # models end in LogSoftMax -> NLL per step (reference PTBWordLM pairs
    # TimeDistributedCriterion with CrossEntropy on raw outputs instead)
    criterion = nn.TimeDistributedCriterion(
        nn.ClassNLLCriterion(), size_average=True)
    optimizer = (optim.LocalOptimizer(model, train_set, criterion)
                 .set_optim_method(optim.Adam(
                     learning_rate=args.learning_rate))
                 .set_end_when(optim.max_epoch(args.max_epoch)))
    optimizer.optimize()
    ppl = float(np.exp(min(optimizer.state["loss"], 20.0)))
    print(f"final: epoch={optimizer.state['epoch']} "
          f"loss={optimizer.state['loss']:.4f} train_ppl={ppl:.1f}")
    return optimizer


if __name__ == "__main__":
    main()
