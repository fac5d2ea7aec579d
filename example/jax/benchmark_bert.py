"""BERT-large MLM training throughput (the reference's headline benchmark,
README.md:35-41 / BASELINE.md) on the byteps_tpu fused DP path.

Run:  python example/jax/benchmark_bert.py [--steps N] [--batch B]
      [--seq L] [--compress-dcn]  (onebit on the inter-slice hop)
      [--tiny]  (bert_tiny at seq 32, batch 2: the CPU smoke size)
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
import numpy as np
import optax

import byteps_tpu as bps
from byteps_tpu.comm.mesh import get_comm
from byteps_tpu.models.bert import (BertForMLM, bert_large, bert_tiny,
                                    mlm_loss, synthetic_batch)
from byteps_tpu.parallel import make_dp_train_step, replicate, shard_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="bert_tiny at smoke sizes instead of BERT-large")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None, help="per device")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--compress-dcn", action="store_true")
    args = ap.parse_args()
    d_steps, d_batch, d_seq = (3, 2, 32) if args.tiny else (20, 32, 128)
    steps = args.steps or d_steps
    per_dev = args.batch or d_batch
    seq = args.seq or d_seq

    bps.init()
    comm = get_comm()
    n = comm.num_ranks
    cfg = bert_tiny() if args.tiny else bert_large()
    model = BertForMLM(cfg)
    rng = jax.random.PRNGKey(0)
    gb = per_dev * n
    batch = synthetic_batch(rng, cfg, batch=gb, seq_len=seq)
    params = model.init(rng, batch["input_ids"][:1],
                        batch["attention_mask"][:1])
    tx = optax.adamw(1e-4)

    def loss_fn(p, b):
        logits = model.apply(p, b["input_ids"], b["attention_mask"],
                             masked_positions=b["masked_positions"])
        return mlm_loss(logits, b["masked_labels"])

    compress = None
    if args.compress_dcn:
        from byteps_tpu.ops import make_onebit_pair
        compress = make_onebit_pair()

    step = make_dp_train_step(comm, loss_fn, tx, compress_dcn=compress)
    params = replicate(comm, params)
    opt_state = replicate(comm, tx.init(params))
    batch = shard_batch(comm, batch)

    params, opt_state, loss = step(params, opt_state, batch)  # compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    eps = steps * gb / dt
    dev = jax.devices()[0]
    print(f"loss {float(loss):.4f}  {eps:.1f} examples/s "
          f"({eps / n:.1f}/device, {n} x {dev.platform} "
          f"{dev.device_kind})")
    bps.shutdown()


if __name__ == "__main__":
    main()
