#!/usr/bin/env python3
"""A Mamba-2 / attention hybrid language model through ``Module.fit`` at
toy size.

``granite_hybrid_symbol`` is the GraniteMoeHybrid block without experts as
a ``Symbol`` (``docs/LM_OPS.md``): nine Mamba-2 mixers to one grouped-query
attention layer without any positional embedding, a biased causal
convolution with SiLU before the scan, an RMSNorm gated by a SiLU branch
after it, a fused-input SwiGLU MLP, the family's four multipliers and a
tied head.  float32 token ids in, the mean next-token loss out, every
layer one recomputation segment; the scan runs in chunks of 8 tokens, so
a sequence of 24 carries its state across two chunk boundaries.  The
documents are walks of a fixed permutation of the vocabulary from a random
start, so the next token is learnable.  Prints ``final loss <x> uniform
<y>``.
"""
import argparse
import math

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models.granite import GRANITE_TINY, granite_hybrid_symbol


def permutation_walks(rng, count, seq_len, vocab):
    step = rng.permutation(vocab)
    ids = np.empty((count, seq_len + 1), np.int64)
    ids[:, 0] = rng.randint(0, vocab, count)
    for t in range(seq_len):
        ids[:, t + 1] = step[ids[:, t]]
    return ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--num-epochs", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=24)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1.0)
    args = ap.parse_args()

    cfg = dict(GRANITE_TINY, vocab_size=16)
    mx.random.seed(7)
    x, y = permutation_walks(np.random.RandomState(7), 256, args.seq_len,
                             cfg["vocab_size"])
    it = mx.io.NDArrayIter(x, y, batch_size=args.batch_size,
                           label_name="softmax_label")
    mod = mx.mod.Module(granite_hybrid_symbol(cfg), context=mx.cpu())
    metric = mx.metric.create("loss")
    mod.fit(it, eval_metric=metric, num_epoch=args.num_epochs,
            initializer=mx.initializer.Xavier(magnitude=2.0),
            optimizer="sgd",
            optimizer_params=(("learning_rate", args.lr),
                              ("momentum", 0.9)))
    print("final loss %.4f uniform %.4f"
          % (metric.get()[1], math.log(cfg["vocab_size"])))


if __name__ == "__main__":
    main()
