"""Path ``engine``: engine-mode ``byteps_tpu.jax.DistributedOptimizer``,
driven as ``chip_smoke.phase_engine_train`` drives it.

Per-rank gradients come from one jitted ``shard_map`` (rank r's examples,
gradients and loss live on chip r); ``opt.update`` hands every leaf to
``core.engine`` (staging, chunking, scheduling, dispatch, sync,
assembly) and runs the optax update on the averaged result; a jitted
``apply_updates`` finishes the step.  The step is host-driven and
synchronous: it returns when the new parameters are on the device.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from harness import checks

PREFIX = "grad"


def _planner_locked(snap: dict) -> bool:
    buckets = snap["planner"]["buckets"]
    return all(b["locked_partition_bytes"] is not None
               for b in buckets.values())


class Runner:
    def __init__(self, job):
        from byteps_tpu.jax import DistributedOptimizer
        self.job = job
        comm, fam, tx = job.comm, job.family, job.tx
        n, per = job.chips, job.seqs_per_chip
        axes = comm.dp_axes
        rep = comm.replicated_sharding()

        def stacked(ndim):
            return comm.stacked_sharding(extra_dims=ndim)

        def stacked_batch(key):
            b = fam.make_batch(key, n * per)
            return jax.tree.map(
                lambda v: v.reshape((n, per) + v.shape[1:]), b)

        ring = int(job.traffic["batch_ring"])
        with job.spans.span("bench.setup.state"):
            self.params = jax.jit(fam.init_params,
                                  out_shardings=rep)(job.param_key)
            shapes = jax.eval_shape(stacked_batch, job.batch_key(0))
            make = jax.jit(stacked_batch, out_shardings=jax.tree.map(
                lambda s: stacked(s.ndim - 1), shapes))
            self.batches = [make(job.batch_key(i)) for i in range(ring)]

        def per_rank(p, b):
            loss, g = jax.value_and_grad(fam.loss_fn)(
                p, jax.tree.map(lambda v: v[0], b))
            return loss[None], jax.tree.map(lambda v: v[None], g)

        grad_fn = jax.jit(
            jax.shard_map(per_rank, mesh=comm.mesh,
                          in_specs=(P(), P(axes)),
                          out_specs=(P(axes), P(axes)), check_vma=False),
            out_shardings=(stacked(0), jax.tree.map(
                lambda v: stacked(v.ndim), self.params)))
        with job.spans.span("bench.setup.compile"):
            self.grad = grad_fn.lower(self.params,
                                      self.batches[0]).compile()
        # The optax update and the apply are the user's own code, jitted
        # as a training script would; the push_pull between them is
        # host-driven.  init stays eager (chip_smoke: a jitted init drops
        # its mesh-placed inputs and parks the state on device 0).
        self.opt = DistributedOptimizer(
            optax.GradientTransformation(tx.init, jax.jit(tx.update)),
            name_prefix=PREFIX)
        self.state = self.opt.init(self.params)
        self.apply = jax.jit(optax.apply_updates, donate_argnums=(0,),
                             out_shardings=rep)
        self.engine_steps = {}     # StepStats by step number

    def step(self, i: int):
        import byteps_tpu as bps
        spans = self.job.spans
        with spans.span("bench.grad"):
            rank_loss, grads = self.grad(
                self.params, self.batches[i % len(self.batches)])
        with spans.span("bench.opt_update"):
            updates, self.state = self.opt.update(grads, self.state,
                                                  self.params)
        del grads
        with spans.span("bench.apply"):
            self.params = self.apply(self.params, updates)
        with spans.span("bench.block"):
            jax.block_until_ready(self.params)
        last = bps.metrics_snapshot(light=True).get("step")
        if last:
            self.engine_steps[last["step"]] = last
        return jnp.mean(rank_loss)

    def warmup(self):
        """Warm until the planner has locked every bucket it touched and
        the engine has compiled nothing for ``warmup_quiet_steps`` steps;
        hitting the cap fails the RUN (not the window)."""
        import byteps_tpu as bps
        t = self.job.traffic
        least, quiet_for = int(t["warmup_steps"]), int(
            t["warmup_quiet_steps"])
        cap = int(t["warmup_cap_steps"])
        losses, quiet, misses = [], 0, None
        for i in range(cap):
            losses.append(float(self.step(i)))
            snap = bps.metrics_snapshot()
            now = snap["counters"].get("engine.compile_cache_miss", 0)
            quiet = quiet + 1 if now == misses else 0
            misses = now
            if (len(losses) >= least and quiet >= quiet_for
                    and _planner_locked(snap)):
                break
        else:
            raise RuntimeError(
                f"engine warm-up hit its cap of {cap} steps: planner "
                f"locked={_planner_locked(snap)}, quiet steps={quiet}")
        plan = snap["planner"]
        print(json.dumps({"info": "engine_plan", "warmup_steps":
                          len(losses), "scheduler": snap.get("scheduler"),
                          "compile_cache_miss": misses,
                          "credit_bytes": plan["credit_bytes"],
                          "buckets": {b: v["locked_partition_bytes"]
                                      for b, v in plan["buckets"].items()}}),
              flush=True)
        return losses, len(losses)

    def hlo_texts(self) -> list:
        return [self.grad.as_text()]

    def checks(self) -> dict:
        """(c) one push_pull of rank-stacked gradients equals their
        float32 mean, leaf by leaf, within what float32 summation order
        allows; (d) batch and gradients span every chip."""
        from byteps_tpu.jax import push_pull
        n = self.job.chips
        self.state = None            # AdamW moments: room for the check
        _, grads = self.grad(self.params, self.batches[0])
        out = {"batch_spans_devices":
               checks.spans_all_devices(self.batches, n),
               "grads_span_devices": checks.spans_all_devices(grads, n),
               "memory_even": checks.memory_even(jax.devices())}
        got = push_pull(grads, PREFIX)
        rtol = checks.sum_order_rtol(n, reductions=1)

        @jax.jit
        def within(got, grads):
            def leaf(o, g):
                g = g.astype(jnp.float32)
                tol = rtol * jnp.mean(jnp.abs(g), axis=0)
                return jnp.all(jnp.abs(o.astype(jnp.float32)
                                       - jnp.mean(g, axis=0)) <= tol)
            return jnp.all(jnp.stack(jax.tree.leaves(
                jax.tree.map(leaf, got, grads))))

        out["push_pull_is_mean"] = bool(within(got, grads))
        return out

    def free(self) -> None:
        self.params = self.state = self.batches = None
        self.grad = self.apply = self.opt = None
