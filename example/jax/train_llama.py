"""Train a Llama-family model under (fsdp, tp) composite sharding.

The flagship modern-LLM configuration (BASELINE.json configs[4]:
"Llama-3-8B"): RoPE/RMSNorm/SwiGLU/GQA decoder with Megatron-style tensor
parallelism inside the fastest ICI dimension and ZeRO-3-by-annotation
parameter sharding (XLA streams each layer's gather) over the rest of the
mesh, batch sharded over the fsdp axis.

    # tiny config on whatever devices are visible (CPU mesh in tests):
    python example/jax/train_llama.py --steps 10

    # the real 8B geometry (needs a pod slice; bf16 + remat):
    python example/jax/train_llama.py --config 8b --tp 4 --batch 8 \
        --seq 4096 --bf16

Per-device persistent memory for the 8B config at (fsdp=16, tp=4):
params 16 GB / 64 + adam 32 GB / 64 = ~0.75 GB, leaving HBM to
activations — the configuration the reference's replicated-optimizer
design cannot express at any cluster size (SURVEY.md §2.6).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

from byteps_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=["tiny", "8b"], default="tiny")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tp", type=int, default=0,
                    help="tp axis size (0 = largest of 4/2/1 dividing "
                         "the device count)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--bf16", action="store_true",
                    help="bf16 activations for the tiny config (which "
                         "defaults to f32 here for CPU parity); the 8b "
                         "config is always bf16 + remat")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from byteps_tpu.models.llama import llama3_8b, llama_tiny
    import byteps_tpu.parallel as par

    devices = jax.devices()
    n = len(devices)
    n_tp = args.tp or max(d for d in (4, 2, 1) if n % d == 0)

    import dataclasses

    if args.config == "8b":
        # always bf16 + remat: seq-4096 x 32-layer activations without
        # remat OOM a pod regardless of flags
        cfg = dataclasses.replace(llama3_8b(), dtype=jnp.bfloat16,
                                  remat=True)
    else:
        cfg = dataclasses.replace(
            llama_tiny(),
            dtype=jnp.bfloat16 if args.bf16 else jnp.float32)

    mesh = par.make_fsdp_tp_mesh(devices, n_tp=n_tp)
    rng = jax.random.PRNGKey(0)
    batch = par.synthetic_lm_batch(rng, cfg, args.batch, args.seq)
    tx = optax.adamw(args.lr)

    t0 = time.perf_counter()
    # sharded init: weights are born on their (fsdp, tp) placement — the
    # 8B tree never exists unsharded on any single device
    params = par.init_llama_params_sharded(mesh, cfg, rng,
                                           batch["input_ids"][:1])
    opt_state = par.init_llama_opt_state(tx, params)
    step = par.make_fsdp_tp_train_step(mesh, cfg, tx)
    batch = par.shard_llama_batch(mesh, batch)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    losses = []
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0

    print(json.dumps({
        "mode": "fsdp_tp", "mesh": {"fsdp": n // n_tp, "tp": n_tp},
        "n_params": n_params, "steps": args.steps,
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "wall_s": round(dt, 2),
    }))
    assert losses[-1] < losses[0], "loss did not decrease"
    return 0


if __name__ == "__main__":
    main()
