"""Measure the TF communication-boundary options (VERDICT r1 item 6).

Three ways to train a TF model through byteps_tpu, timed on the same
model/batch so the decision in docs/performance.md is recorded with data:

1. ``nocomm_jit``      — tf.function(jit_compile=True), no communication:
                         the compute lower bound.
2. ``boundary_jit``    — make_compiled_train_step: XLA-compiled
                         forward/backward and apply, engine push_pull at
                         the program boundary (the TPU-native pattern).
3. ``ingraph_pyfunc``  — DistributedGradientTape inside tf.function
                         (jit_compile NOT possible): the round-1 path,
                         matching the reference's in-graph placement
                         (reference tensorflow/ops.cc:167-231).

Run: python example/tensorflow/bench_compiled_boundary.py [--steps N]
Prints one JSON line with steps/s per configuration and the overhead of
each communication placement vs the no-comm bound.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _model(tf):
    # a real (if small) model: 4-block MLP-mixer-ish tower, ~1.1M params
    inputs = tf.keras.Input((256,))
    h = inputs
    for _ in range(4):
        h = tf.keras.layers.Dense(512, activation="gelu")(h)
    outputs = tf.keras.layers.Dense(10)(h)
    return tf.keras.Model(inputs, outputs)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    import numpy as np
    import tensorflow as tf

    import byteps_tpu.tensorflow as bps_tf

    tf.random.set_seed(0)
    bps_tf.init()
    loss_fn = tf.keras.losses.SparseCategoricalCrossentropy(from_logits=True)
    rng = np.random.RandomState(0)
    x = tf.constant(rng.randn(64, 256).astype(np.float32))
    y = tf.constant(rng.randint(0, 10, 64).astype(np.int64))

    def time_steps(step, n):
        step(x, y)  # warmup/trace/compile
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step(x, y)
        _ = float(loss)
        return n / (time.perf_counter() - t0)

    results = {}

    # 1. no-comm jit bound
    m1 = _model(tf)
    o1 = tf.keras.optimizers.SGD(0.01)

    @tf.function(jit_compile=True)
    def step_nocomm(xb, yb):
        with tf.GradientTape() as tape:
            loss = loss_fn(yb, m1(xb, training=True))
        o1.apply_gradients(zip(tape.gradient(loss, m1.trainable_variables),
                               m1.trainable_variables))
        return loss
    results["nocomm_jit"] = time_steps(step_nocomm, args.steps)

    # 2. compiled boundary
    m2 = _model(tf)
    o2 = tf.keras.optimizers.SGD(0.01)
    step_boundary = bps_tf.make_compiled_train_step(
        m2, lambda logits, yb: loss_fn(yb, logits), o2)

    def step2(xb, yb):
        return step_boundary(xb, yb)
    results["boundary_jit"] = time_steps(step2, args.steps)

    # 3. in-graph py_function (cannot jit_compile)
    m3 = _model(tf)
    o3 = tf.keras.optimizers.SGD(0.01)

    @tf.function
    def step_ingraph(xb, yb):
        with bps_tf.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = loss_fn(yb, m3(xb, training=True))
        o3.apply_gradients(zip(tape.gradient(loss, m3.trainable_variables),
                               m3.trainable_variables))
        return loss
    results["ingraph_pyfunc"] = time_steps(step_ingraph, args.steps)

    bps_tf.shutdown()
    bound = results["nocomm_jit"]
    out = {k: round(v, 2) for k, v in results.items()}
    out["boundary_overhead_pct"] = round(
        100 * (1 - results["boundary_jit"] / bound), 1)
    out["ingraph_overhead_pct"] = round(
        100 * (1 - results["ingraph_pyfunc"] / bound), 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
