"""What decides ``correct``.

The plain reference step is independent of the code under test: each
family's float32 ``jax.numpy`` forward, ``jax.value_and_grad`` on each
shard in turn on ONE device, a float32 mean of the gradients,
``tx.update``, ``optax.apply_updates`` — none of the repo's models, step
builders or collectives; the same seed, the same batches.
"""

from __future__ import annotations

import numpy as np

# bf16 compute (8 mantissa bits) against a float32 reference, summed in
# another order: the losses agree to ~1e-3; PR 21 saw 1e-4 between the
# repo's two paths.  1e-2 would still fail a model computed in fp8, a
# dropped layer or a wrong mask, whose losses differ in the first digit.
LOSS_RTOL = 1e-2


def sum_order_rtol(n_terms: int, reductions: int) -> float:
    """Relative tolerance between two float32 sums of ``n_terms`` taken
    in different orders: each is within ~log2(n)*eps/2 of the true sum
    (after chip_smoke.sum_order_rtol)."""
    return float(reductions * np.log2(max(2, n_terms))
                 * np.finfo(np.float32).eps)


def spans_all_devices(tree, n: int) -> bool:
    import jax
    return all(len(leaf.sharding.device_set) == n
               for leaf in jax.tree.leaves(tree))


def memory_even(devices) -> bool:
    """Every chip holds the same replicated state plus an equal shard:
    the emptiest within 20% of the fullest, or a tree is parked on one
    device (after chip_smoke._memory_spread)."""
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if len(devices) < 2 or any(u is None for u in used):
        return True
    return min(used) >= 0.8 * max(used)


def peak_bytes(devices):
    """Peak device memory on the fullest chip, or None where the backend
    reports none (CPU).  On this runtime ``peak_bytes_in_use`` counts live
    arrays only; the scratch an XLA program reserves while it runs
    (activations, fusion temporaries) is ``peak_bytes_reserved``.  Their
    sum is the high-water mark a user has to fit (an upper bound where
    the two peaks fall at different moments): 9.3 GiB for BERT-large at
    64/chip where ``peak_bytes_in_use`` alone reads 4.4 (PR 22)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(stats["peak_bytes_in_use"]
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


def reference_losses(family, tx, key, batch_key_for, n_steps: int,
                     global_seqs: int, micro: int, device) -> list:
    """Losses of the first ``n_steps`` steps from the seeded initial
    parameters, by the plain reference, on ``device`` alone."""
    import jax
    import jax.numpy as jnp
    import optax

    if global_seqs % micro:
        raise ValueError(f"reference microbatch {micro} does not divide "
                         f"the global batch {global_seqs}")
    n_micro = global_seqs // micro
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        params = jax.jit(family.init_params)(key)
        state = jax.jit(tx.init)(params)
        grad_fn = jax.jit(jax.value_and_grad(family.reference_loss))
        make = jax.jit(family.make_batch, static_argnums=1)
        add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                      donate_argnums=0)

        def apply(params, state, gsum):
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            updates, state = tx.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        apply = jax.jit(apply, donate_argnums=(0, 1, 2))

        losses = []
        for i in range(n_steps):
            batch = make(batch_key_for(i), global_seqs)
            total, gsum = 0.0, None
            for m in range(n_micro):
                mb = jax.tree.map(
                    lambda x: x[m * micro:(m + 1) * micro], batch)
                loss, g = grad_fn(params, mb)
                total += float(loss)
                gsum = g if gsum is None else add(gsum, g)
                del g
            params, state = apply(params, state, gsum)
            losses.append(total / n_micro)
    return losses


def losses_agree(got, want) -> bool:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return bool(got.shape == want.shape and np.all(np.isfinite(got))
                and np.allclose(got, want, rtol=LOSS_RTOL, atol=0.0))
