"""Cross-barrier pipelining benchmark (reference
example/pytorch/benchmark_cross_barrier_byteps.py): remove the
end-of-iteration barrier so communication overlaps the *next* forward
pass; per-layer averaged gradients are applied just-in-time.

Run:  python example/pytorch/benchmark_cross_barrier_byteps.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse
import time

import torch
import torch.nn.functional as F

import byteps_tpu.torch as bps
from byteps_tpu.torch.parallel import CrossBarrier


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    bps.init()
    model = torch.nn.Sequential(
        torch.nn.Linear(1024, 2048), torch.nn.ReLU(),
        torch.nn.Linear(2048, 2048), torch.nn.ReLU(),
        torch.nn.Linear(2048, 1000))
    opt = torch.optim.SGD(model.parameters(), lr=0.01)
    xb = CrossBarrier(model, opt)

    x = torch.randn(args.batch, 1024)
    y = torch.randint(0, 1000, (args.batch,))

    F.cross_entropy(model(x), y).backward()  # warm-up
    xb.step()
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        opt.zero_grad()
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        xb.step()  # returns immediately; grads applied at next forward
    xb.synchronize()  # drain before timing stops
    dt = time.perf_counter() - t0
    print(f"{args.num_iters * args.batch / dt:.1f} examples/s "
          f"with cross-barrier overlap")
    bps.shutdown()


if __name__ == "__main__":
    main()
