"""Elastic training demo (reference
example/pytorch/elastic_benchmark_byteps.py): suspend() mid-training,
then resume() — declared tensors keep their key order, so training
continues with identical scheduling.

Run:  python example/pytorch/elastic_benchmark_byteps.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import torch
import torch.nn.functional as F

import byteps_tpu as bps_core
import byteps_tpu.torch as bps


def main():
    bps.init()
    model = torch.nn.Linear(256, 10)
    opt = bps.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    x = torch.randn(64, 256)
    y = torch.randint(0, 10, (64,))

    def train(steps):
        for _ in range(steps):
            opt.zero_grad()
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
        return float(loss.detach())

    print("phase 1 loss:", round(train(5), 4))
    bps_core.suspend()          # drain engine, drop mesh
    print("suspended (simulating topology change)...")
    bps_core.resume()           # re-init; keys re-declared in order
    print("resumed")
    print("phase 2 loss:", round(train(5), 4))
    bps.shutdown()


if __name__ == "__main__":
    main()
