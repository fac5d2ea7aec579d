"""Keras MNIST-class training with byteps_tpu callbacks (reference
example/keras/keras_mnist.py, synthetic data).

Run:  python example/keras/keras_mnist.py [--epochs N]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse

import numpy as np
import tensorflow as tf

import byteps_tpu.keras as bps_keras
import byteps_tpu.tensorflow as bps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()

    bps.init()
    rng = np.random.RandomState(bps.rank())
    x = rng.randn(512, 784).astype(np.float32)
    y = rng.randint(0, 10, 512)

    model = tf.keras.Sequential([
        tf.keras.layers.Dense(128, activation="relu"),
        tf.keras.layers.Dense(10, activation="softmax"),
    ])
    # scale lr by worker count (reference keras examples do the same)
    opt = tf.keras.optimizers.SGD(0.05 * bps.size())
    opt = bps_keras.DistributedOptimizer(opt)
    model.compile(optimizer=opt,
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"],
                  run_eagerly=True)  # engine hop is a host callback

    callbacks = [
        bps_keras.callbacks.BroadcastGlobalVariablesCallback(0),
        bps_keras.callbacks.MetricAverageCallback(),
        bps_keras.callbacks.LearningRateWarmupCallback(
            warmup_epochs=1, verbose=0),
    ]
    model.fit(x, y, batch_size=args.batch, epochs=args.epochs,
              callbacks=callbacks,
              verbose=2 if bps.rank() == 0 else 0)
    bps.shutdown()


if __name__ == "__main__":
    main()
