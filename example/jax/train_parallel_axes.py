"""Train a small GPT (or MoE layer) under each composite parallelism axis.

The byteps_tpu counterpart of "which axis do I reach for": the same tiny
model runs under (dp,tp) GSPMD, (dp,pp) GPipe, a (dp,ep) switch-MoE
regression, ZeRO-1/FSDP sharded-optimizer DP, or the full 3D
(dp,pp,tp) composite — all on whatever devices are visible (8 virtual
CPU devices in tests; a real slice in production).

    python example/jax/train_parallel_axes.py --mode tp --steps 10
    python example/jax/train_parallel_axes.py --mode pp --microbatches 4
    python example/jax/train_parallel_axes.py --mode ep --experts 8
    python example/jax/train_parallel_axes.py --mode zero
    python example/jax/train_parallel_axes.py --mode fsdp
    python example/jax/train_parallel_axes.py --mode 3d --microbatches 2
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
    ap.add_argument("--mode", choices=["tp", "pp", "ep", "zero", "fsdp",
                                       "3d"], default="tp")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--inner", type=int, default=0,
                    help="size of the tp/pp/ep axis (0 = largest of "
                         "4/2/1 that divides the device count)")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from byteps_tpu.models.gpt import GPT, GPTConfig
    import byteps_tpu.parallel as par

    devices = jax.devices()
    n = len(devices)
    # default inner axis: largest size that divides both the device count
    # and the model's shardable dims (4 heads / 4 layers)
    inner = args.inner or max(d for d in (4, 2, 1) if n % d == 0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                    num_heads=4, intermediate_size=128, max_position=256,
                    dtype=jnp.float32)
    tx = optax.adam(1e-2)
    rng = jax.random.PRNGKey(0)

    t0 = time.perf_counter()
    if args.mode == "tp":
        mesh = par.make_tp_mesh(devices, n_tp=inner)
        b = par.synthetic_lm_batch(rng, cfg, args.batch, args.seq)
        p = par.shard_gpt_params(
            mesh, GPT(cfg).init(rng, b["input_ids"][:1]))
        o = par.init_tp_opt_state(tx, p)
        step = par.make_dp_tp_train_step(mesh, cfg, tx)
        b = par.shard_tp_batch(mesh, b)
    elif args.mode == "pp":
        mesh = par.make_pp_mesh(devices, n_pp=inner)
        b = par.synthetic_lm_batch(rng, cfg, args.batch, args.seq)
        p = par.shard_pipeline_params(
            mesh, par.init_pipeline_params(cfg, rng, b["input_ids"][:1]))
        o = jax.jit(tx.init)(p)
        step = par.make_dp_pp_train_step(
            mesh, cfg, tx, num_microbatches=args.microbatches)
        b = par.shard_pp_batch(mesh, b)
    elif args.mode in ("zero", "fsdp"):
        # sharded-optimizer DP: master vector + moments live 1/R across
        # the whole mesh; fsdp additionally stores params only sharded
        from byteps_tpu.comm.mesh import CommContext, _build_mesh
        comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)
        b = par.synthetic_lm_batch(rng, cfg, args.batch, args.seq)
        model = GPT(cfg)
        params = model.init(rng, b["input_ids"][:1])

        def loss_fn(p, bb):
            from byteps_tpu.models.gpt import lm_loss
            return lm_loss(model.apply(p, bb["input_ids"]), bb["labels"])

        zstate = par.init_zero_state(comm, tx, params)
        b = par.shard_batch(comm, b)
        if args.mode == "zero":
            zstep = par.make_zero_train_step(comm, loss_fn, tx)
            zp = par.replicate(comm, params)

            def step(p, o, bb):
                nonlocal zp
                zp, z, loss = zstep(zp, o, bb)
                return p, z, loss
        else:
            fstep = par.make_fsdp_train_step(comm, loss_fn, tx,
                                             params_template=params)

            def step(p, o, bb):
                z, loss = fstep(o, bb)
                return p, z, loss
        p, o = None, zstate
        mesh = comm.mesh
    elif args.mode == "3d":
        # honor --inner as the tp size when it fits (pp fixed at 2 when
        # the device count allows); degrade to trivial axes on small or
        # odd device counts rather than crashing
        if args.inner and n % (2 * args.inner) == 0 \
                and cfg.num_heads % args.inner == 0:
            n_tp = args.inner
        else:
            n_tp = max((d for d in (2, 1) if n % (2 * d) == 0), default=1)
        n_pp = 2 if n % (2 * n_tp) == 0 else 1
        inner = n_tp  # reported layout matches what actually ran
        mesh = par.make_3d_mesh(devices, n_pp=n_pp, n_tp=n_tp)
        b = par.synthetic_lm_batch(rng, cfg, args.batch, args.seq)
        p = par.shard_3d_params(
            mesh, par.init_pipeline_params(cfg, rng, b["input_ids"][:1]))
        o = par.init_3d_opt_state(tx, p)
        step = par.make_dp_pp_tp_train_step(
            mesh, cfg, tx, num_microbatches=args.microbatches)
        b = par.shard_3d_batch(mesh, b)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = par.make_ep_mesh(devices, n_ep=inner)
        hidden = cfg.hidden_size
        p = par.shard_moe_params(mesh, par.init_moe_params(
            rng, hidden, cfg.intermediate_size, args.experts))
        o = jax.jit(tx.init)(p)
        step = par.make_dp_ep_train_step(
            mesh, args.experts, 1.5, tx,
            lambda out, bb: jnp.mean((out - bb["y"]) ** 2))
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (args.batch * n, hidden))
        b = jax.device_put({"x": x, "y": jnp.tanh(x[:, ::-1])},
                           NamedSharding(mesh, P(("dp", "ep"))))

    losses = []
    for _ in range(args.steps):
        p, o, loss = step(p, o, b)
        losses.append(float(loss))
    assert np.isfinite(losses[-1])
    layout = {"3d": lambda: f"pp{mesh.shape['pp']}xtp{mesh.shape['tp']}"}
    print(json.dumps({
        "mode": args.mode, "n_devices": n,
        "inner_axis": layout.get(args.mode, lambda: inner)(),
        "first_loss": round(losses[0], 4), "last_loss": round(losses[-1], 4),
        "wall_s": round(time.perf_counter() - t0, 2),
    }))
    return 0


if __name__ == "__main__":
    main()
