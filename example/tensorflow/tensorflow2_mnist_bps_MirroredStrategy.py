"""BytePS-backed MirroredStrategy (reference
example/tensorflow/tensorflow2_mnist_bps_MirroredStrategy.py): replica
reduction routes through the engine's push_pull.

Run:  python example/tensorflow/tensorflow2_mnist_bps_MirroredStrategy.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse

import numpy as np
import tensorflow as tf

import byteps_tpu.tensorflow as bps
from byteps_tpu.tensorflow.distribute import MirroredStrategy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()

    strategy = MirroredStrategy()  # engine cross-device ops installed
    with strategy.scope():
        model = tf.keras.Sequential([
            tf.keras.layers.Dense(128, activation="relu"),
            tf.keras.layers.Dense(10),
        ])
        opt = tf.keras.optimizers.SGD(0.05)

    rng = np.random.RandomState(0)
    x = tf.constant(rng.randn(args.batch, 784).astype(np.float32))
    y = tf.constant(rng.randint(0, 10, args.batch))

    @tf.function
    def step():
        def replica_fn():
            with tf.GradientTape() as tape:
                logits = model(x, training=True)
                loss = tf.reduce_mean(
                    tf.nn.sparse_softmax_cross_entropy_with_logits(
                        y, logits))
            grads = tape.gradient(loss, model.trainable_variables)
            opt.apply_gradients(zip(grads, model.trainable_variables))
            return loss
        return strategy.run(replica_fn)

    for i in range(args.steps):
        loss = strategy.reduce(tf.distribute.ReduceOp.MEAN, step(),
                               axis=None)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
