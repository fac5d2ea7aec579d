"""Gluon MNIST training via byteps_tpu.mxnet DistributedTrainer
(reference example/mxnet/train_gluon_mnist_byteps.py, synthetic data).
Requires mxnet (pip install mxnet); the adapter itself does not.

Run:  python example/mxnet/train_gluon_mnist_byteps.py [--epochs N]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse

import numpy as np

import byteps_tpu.mxnet as bps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()

    import mxnet as mx
    from mxnet import autograd, gluon

    bps.init()
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())

    trainer = bps.DistributedTrainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.05 * bps.size()})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    rng = np.random.RandomState(bps.rank())
    x = mx.nd.array(rng.randn(args.batch, 784).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, args.batch))

    for i in range(args.steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(args.batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss.mean().asscalar()):.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
