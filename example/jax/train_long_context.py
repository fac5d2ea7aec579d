"""Long-context causal-LM training over a (dp, sp) mesh.

What the reference cannot do at all (no sequence dimension anywhere,
SURVEY.md §5): sequence length is sharded across devices, attention runs
as a ring (K/V blocks rotating over ICI) or Ulysses (all-to-all head
resharding), and gradients are push_pulled over both mesh axes — one
jitted step.

Run:  python example/jax/train_long_context.py --seq 8192 --sp 4
CPU smoke:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python example/jax/train_long_context.py \
    --steps 3 --seq 256 --sp 4 --tiny
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
import optax

from byteps_tpu.models.gpt import GPT, gpt_small, gpt_tiny
from byteps_tpu.parallel import (make_dp_sp_train_step, make_sp_mesh,
                                 shard_lm_batch, synthetic_lm_batch)
from byteps_tpu.parallel.long_context import replicate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--sp", type=int, default=None,
                    help="sequence-parallel degree (default: all devices)")
    ap.add_argument("--attention",
                choices=("ring", "striped", "ring_flash", "ulysses",
                         "ulysses_flash"),
                    default="ring")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    cfg = gpt_tiny() if args.tiny else gpt_small()
    mesh = make_sp_mesh(n_sp=args.sp)
    n_dp, n_sp = mesh.devices.shape
    print(f"mesh: dp={n_dp} x sp={n_sp}, seq {args.seq} "
          f"({args.seq // n_sp}/device), attention={args.attention}")

    rng = jax.random.PRNGKey(0)
    batch = synthetic_lm_batch(rng, cfg, batch=args.batch,
                               seq_len=args.seq)
    params = GPT(cfg).init(rng, batch["input_ids"][:1, : args.seq])
    tx = optax.adamw(3e-4)
    step = make_dp_sp_train_step(mesh, cfg, tx, attention=args.attention)

    p = replicate(mesh, params)
    o = replicate(mesh, tx.init(params))
    b = shard_lm_batch(mesh, batch, striped=args.attention == "striped")

    p, o, loss = step(p, o, b)  # compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(args.steps):
        p, o, loss = step(p, o, b)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq
    print(f"{toks / dt:.0f} tokens/s")


if __name__ == "__main__":
    main()
