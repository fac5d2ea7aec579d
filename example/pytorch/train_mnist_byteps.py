"""MNIST-class training with the torch adapter (reference
example/pytorch/train_mnist_byteps.py, synthetic data).

Run:  python example/pytorch/train_mnist_byteps.py [--epochs N]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import argparse

import torch
import torch.nn.functional as F

import byteps_tpu.torch as bps


class Net(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = torch.nn.Linear(784, 128)
        self.fc2 = torch.nn.Linear(128, 10)

    def forward(self, x):
        return self.fc2(torch.relu(self.fc1(x)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    bps.init()
    torch.manual_seed(bps.rank())  # different data per worker
    model = Net()
    opt = torch.optim.SGD(model.parameters(), lr=args.lr, momentum=0.9)
    opt = bps.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    # consistent start across workers (reference broadcast_parameters)
    bps.broadcast_parameters(model.state_dict(), root_rank=0)

    x = torch.randn(args.batch, 784)
    y = torch.randint(0, 10, (args.batch,))
    for i in range(args.steps):
        opt.zero_grad()
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss.detach()):.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
