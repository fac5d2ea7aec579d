"""Train an MNIST-class MLP with the fused data-parallel path.

The JAX equivalent of the reference's example/pytorch/train_mnist_byteps.py:
the whole step (forward + backward + push_pull + sgd) is one XLA program
over the (dcn, ici) mesh.  Synthetic data (no dataset download).

Run:  python example/jax/train_mnist_mlp.py [--steps N] [--batch B]
CPU smoke:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
            JAX_PLATFORMS=cpu python example/jax/train_mnist_mlp.py --steps 3
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

from byteps_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import byteps_tpu as bps
from byteps_tpu.comm.mesh import get_comm
from byteps_tpu.models.mlp import mnist_mlp, softmax_cross_entropy
from byteps_tpu.parallel import make_dp_train_step, replicate, shard_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32, help="per-device")
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    bps.init()
    comm = get_comm()
    n = comm.num_ranks
    print(f"devices={n} mesh=({comm.n_dcn} dcn x {comm.n_ici} ici)")

    model = mnist_mlp()
    rng = np.random.RandomState(0)
    gb = args.batch * n
    x = jnp.asarray(rng.randn(gb, 784).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=(gb,)))

    params = model.init(jax.random.PRNGKey(0), x[:1])
    tx = optax.sgd(args.lr, momentum=0.9)

    def loss_fn(p, batch):
        logits = model.apply(p, batch["x"])
        return softmax_cross_entropy(logits, batch["y"]).mean()

    step = make_dp_train_step(comm, loss_fn, tx)
    params = replicate(comm, params)
    opt_state = replicate(comm, tx.init(params))
    batch = shard_batch(comm, {"x": x, "y": y})

    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    print(f"{args.steps / dt:.1f} steps/s, "
          f"{args.steps * gb / dt:.0f} examples/s")
    bps.shutdown()


if __name__ == "__main__":
    main()
