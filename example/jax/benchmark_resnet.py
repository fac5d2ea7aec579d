"""Synthetic ResNet-50 / VGG-16 throughput benchmark (images/s).

The rebuild's counterpart of the reference's synthetic benchmarks
(reference example/pytorch/benchmark_byteps.py, docs/performance.md:3-23
table): trains on random NHWC images through the fused DP step with
cross-replica BatchNorm and reports images/s per chip.

    python example/jax/benchmark_resnet.py --model resnet50 --batch 32
    python example/jax/benchmark_resnet.py --model vgg16 --steps 10
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
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "resnet18", "vgg16", "tiny"])
    ap.add_argument("--batch", type=int, default=32, help="per device")
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (smoke runs)")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax

    from byteps_tpu.comm.mesh import CommContext, _build_mesh
    from byteps_tpu.models import resnet as R
    from byteps_tpu.parallel import shard_batch

    devices = jax.devices()
    n = len(devices)
    comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)

    if args.model == "tiny":
        model = R.resnet_tiny(axis_name=comm.dp_axes)
        args.size, classes = min(args.size, 32), 10
    elif args.model == "vgg16":
        model, classes = R.vgg16(), 1000
    elif args.model == "resnet18":
        model = R.resnet18(axis_name=comm.dp_axes)
        classes = 1000
    else:
        model = R.resnet50(axis_name=comm.dp_axes)
        classes = 1000

    rng = jax.random.PRNGKey(0)
    global_batch = args.batch * n
    batch = R.synthetic_images(rng, global_batch, args.size, classes)
    step, state = R.make_vision_trainer(
        comm, model, optax.sgd(0.1, momentum=0.9), batch, rng)
    batch = shard_batch(comm, batch)

    def run(k):
        nonlocal state
        t0 = time.perf_counter()
        loss = None
        for _ in range(k):
            state, loss = step(state, batch)
        jax.block_until_ready(state)
        return time.perf_counter() - t0, float(loss)

    run(2)  # compile + warm
    dt, loss = run(args.steps)
    assert np.isfinite(loss), "non-finite loss"
    ips = args.steps * global_batch / dt
    print(json.dumps({
        "model": args.model, "images_per_sec": round(ips, 2),
        "per_chip": round(ips / n, 2), "n_devices": n,
        "batch_per_device": args.batch, "image_size": args.size,
        "loss": round(loss, 4),
    }))
    return 0


if __name__ == "__main__":
    main()
