"""TF2 synthetic push_pull benchmark (reference
example/tensorflow/synthetic_benchmark_tf2.py).

Run:  python example/tensorflow/synthetic_benchmark_tf2.py [--num-iters N]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse
import time

import numpy as np
import tensorflow as tf

import byteps_tpu.tensorflow as bps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--tensor-mb", type=float, default=4.0)
    ap.add_argument("--num-tensors", type=int, default=10)
    args = ap.parse_args()

    bps.init()
    n = int(args.tensor_mb * 1e6 / 4)
    ts = [tf.constant(np.random.randn(n).astype(np.float32))
          for _ in range(args.num_tensors)]

    for i, t in enumerate(ts):  # warm-up / declare
        bps.push_pull(t, name=f"bench.{i}")
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        for i, t in enumerate(ts):
            bps.push_pull(t, name=f"bench.{i}")
    dt = time.perf_counter() - t0
    mb = args.num_iters * args.num_tensors * args.tensor_mb
    print(f"{mb / dt:.1f} MB/s pushed+pulled")
    bps.shutdown()


if __name__ == "__main__":
    main()
