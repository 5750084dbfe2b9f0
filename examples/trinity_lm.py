#!/usr/bin/env python3
"""A window-and-full-attention sparse-expert language model through
``Module.fit`` at toy size.

``afmoe_symbol`` is the AFMoE (Trinity) block as a ``Symbol``
(``docs/LM_OPS.md``): three sliding-window layers to one full layer, a
sigmoid gate on the heads' output, four RMSNorms a layer, and from the
third layer on a shared expert beside dropless top-k routed experts, of
which this toy holds 4 of the 8 the router scores.  float32 token ids in,
the mean next-token loss out, every layer one recomputation segment.  The
documents are walks of a fixed permutation of the vocabulary from a random
start, so the next token is learnable.  Prints ``final loss <x> uniform
<y>``.
"""
import argparse
import math

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models.trinity import AFMOE_TINY, afmoe_symbol


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
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    cfg = dict(AFMOE_TINY, vocab_size=16)     # window 5 < seq-len
    mx.random.seed(7)
    x, y = permutation_walks(np.random.RandomState(7), 256, args.seq_len,
                             cfg["vocab_size"])
    it = mx.io.NDArrayIter(x, y, batch_size=args.batch_size,
                           label_name="softmax_label")
    mod = mx.mod.Module(afmoe_symbol(cfg), context=mx.cpu())
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
