#!/usr/bin/env python
"""ImageNet-scale training driver (ResNet/Inception zoo).

Parity target: reference ``example/image-classification/train_imagenet.py``
including its synthetic-data benchmark mode (``--benchmark 1``,
README.md:255-260) — the harness behind the headline throughput tables
(README.md:293-320).

Real data: point --data-train at a RecordIO file packed by
``native/bin/im2rec`` (read through the native threaded decode pipeline).
Benchmark mode feeds synthetic batches so it measures pure train-step
throughput.

    python examples/train_imagenet.py --benchmark 1 --network resnet50_v1 \
        --batch-size 32 --num-batches 50
"""
import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


class SyntheticIter(object):
    """Fixed random batch, served repeatedly (the reference's benchmark
    dummy iterator)."""

    def __init__(self, batch_size, image_shape, num_classes, num_batches):
        import mxnet_tpu as mx
        from mxnet_tpu.io import DataBatch, DataDesc
        rng = np.random.RandomState(0)
        data = rng.rand(batch_size, *image_shape).astype(np.float32)
        label = rng.randint(0, num_classes, batch_size).astype(np.float32)
        self._batch = DataBatch(
            [mx.nd.array(data)], [mx.nd.array(label)], pad=0,
            provide_data=[DataDesc("data", (batch_size,) + image_shape)],
            provide_label=[DataDesc("softmax_label", (batch_size,))])
        self.provide_data = self._batch.provide_data
        self.provide_label = self._batch.provide_label
        self.batch_size = batch_size
        self._total = num_batches
        self._served = 0

    def reset(self):
        self._served = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        if self._served >= self._total:
            raise StopIteration
        self._served += 1
        return self._batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="resnet50_v1")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--num-epochs", type=int, default=1)
    ap.add_argument("--num-batches", type=int, default=50,
                    help="benchmark batches per epoch")
    ap.add_argument("--benchmark", type=int, default=0)
    ap.add_argument("--data-train", default=None,
                    help=".rec file for real training data")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--kv-store", default="local")
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"))
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo import vision

    image_shape = tuple(int(x) for x in args.image_shape.split(","))

    if args.benchmark:
        train_iter = SyntheticIter(args.batch_size, image_shape,
                                   args.num_classes, args.num_batches)
    elif args.data_train:
        from mxnet_tpu.image import ImageRecordIter
        train_iter = ImageRecordIter(
            path_imgrec=args.data_train, data_shape=image_shape,
            batch_size=args.batch_size, shuffle=True, rand_mirror=True,
            preprocess_threads=4)
    else:
        ap.error("need --benchmark 1 or --data-train")

    net = vision.get_model(args.network, classes=args.num_classes)
    net.collect_params().initialize(mx.init.Xavier())
    if args.dtype == "bfloat16":
        net.cast("bfloat16")
    net.hybridize()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 1e-4}, kvstore=args.kv_store or None)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for epoch in range(args.num_epochs):
        train_iter.reset()
        tic = time.time()
        n_img = 0
        warm_done = 0.0
        for i, batch in enumerate(train_iter):
            x, y = batch.data[0], batch.label[0]
            if args.dtype == "bfloat16":
                x = x.astype("bfloat16")
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            if i == 2:          # exclude compile/warmup from the rate
                loss.wait_to_read()
                warm_done = time.time()
                n_img = 0
            n_img += x.shape[0]
        loss.wait_to_read()
        toc = time.time()
        span = toc - (warm_done or tic)
        logging.info("epoch %d: %.1f img/s (%d images, %.1fs)",
                     epoch, n_img / span, n_img, span)
    # no context is named anywhere above: everything landed on the default
    # context (accelerator 0 when one is attached, else the host)
    weight = next(iter(net.collect_params().values())).data()
    print("parameters on %s (%s)" % (weight.context, ", ".join(
        str(d) for d in weight._data.devices())))
    print("final-throughput: %.2f img/s" % (n_img / span))
    return n_img / span


if __name__ == "__main__":
    main()
