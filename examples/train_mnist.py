#!/usr/bin/env python
"""Train LeNet/MLP on MNIST (reference: example/image-classification/train_mnist.py).

Uses the idx files if present in --data-dir, else the deterministic
synthetic dataset.  Runs on one TPU chip by default; --cpus N uses a
virtual CPU mesh for data parallelism.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import logging

import mxnet_tpu as mx
from mxnet_tpu import models


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--network", default="lenet", choices=["lenet", "mlp"])
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--num-epochs", type=int, default=5)
    parser.add_argument("--data-dir", default=".")
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--gpus", default=None,
                        help="comma-separated device ids, e.g. 0 or 0,1,2,3")
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)

    if args.network == "lenet":
        net = models.get_lenet(num_classes=10)
        flat = False
    else:
        net = models.get_mlp(num_classes=10)
        flat = True

    train = mx.io.MNISTIter(
        image="%s/train-images-idx3-ubyte" % args.data_dir,
        label="%s/train-labels-idx1-ubyte" % args.data_dir,
        batch_size=args.batch_size, flat=flat, seed=0)
    val = mx.io.MNISTIter(
        image="%s/t10k-images-idx3-ubyte" % args.data_dir,
        label="%s/t10k-labels-idx1-ubyte" % args.data_dir,
        batch_size=args.batch_size, flat=flat, seed=1)

    # the example picks its platform itself: mx.tpu(i) raises without chip i
    import jax

    make_ctx = mx.cpu if jax.devices()[0].platform == "cpu" else mx.tpu
    if args.gpus:
        ctx = [make_ctx(int(i)) for i in args.gpus.split(",")]
    else:
        ctx = make_ctx()

    mod = mx.mod.Module(net, context=ctx)
    mod.fit(train, eval_data=val,
            initializer=mx.initializer.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            kvstore=args.kv_store,
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 50),
            num_epoch=args.num_epochs)
    print("final validation:", mod.score(val, "acc"))


if __name__ == "__main__":
    main()
