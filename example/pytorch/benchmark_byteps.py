"""Synthetic gradient push_pull benchmark, torch frontend (reference
example/pytorch/benchmark_byteps.py shape: timed push_pull of
model-sized gradients, optional compression).

Run:  python example/pytorch/benchmark_byteps.py [--num-iters N]
      [--compressor onebit|topk|randomk|dithering]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse
import time

import torch

import byteps_tpu.torch as bps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--num-tensors", type=int, default=20)
    ap.add_argument("--tensor-mb", type=float, default=4.0)
    ap.add_argument("--compressor", default=None)
    args = ap.parse_args()

    bps.init()
    n_elem = int(args.tensor_mb * 1e6 / 4)
    grads = [torch.randn(n_elem) for _ in range(args.num_tensors)]
    comp = {"compressor": args.compressor} if args.compressor else None
    if comp and args.compressor in ("topk", "randomk"):
        comp["k"] = str(max(1, n_elem // 100))

    # warm-up (compilation)
    hs = [bps.push_pull_async(g, name=f"bench.{i}", compression=comp)
          for i, g in enumerate(grads)]
    for h in hs:
        bps.synchronize(h)

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        hs = [bps.push_pull_async(g, name=f"bench.{i}", compression=comp)
              for i, g in enumerate(grads)]
        for h in hs:
            bps.synchronize(h)
    dt = time.perf_counter() - t0
    total_mb = args.num_iters * args.num_tensors * args.tensor_mb
    print(f"{total_mb / dt:.1f} MB/s pushed+pulled "
          f"({args.num_tensors} x {args.tensor_mb} MB x "
          f"{args.num_iters} iters in {dt:.2f}s)")
    print("engine telemetry:", bps.size() and
          __import__("byteps_tpu").get_pushpull_speed())
    bps.shutdown()


if __name__ == "__main__":
    main()
