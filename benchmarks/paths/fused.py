"""Path ``fused``: the in-graph step of ``parallel.make_dp_train_step``.

Forward, backward, gradient push_pull (an in-graph all-reduce over the
mesh) and the optax update are ONE XLA program; parameters and optimizer
state are replicated and donated, the batch is sharded over every chip.
The engine is initialised (``bps.init``) but no tensor goes through it.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from harness import checks


class Runner:
    def __init__(self, job):
        from byteps_tpu.parallel import make_dp_train_step
        self.job = job
        comm, fam, tx = job.comm, job.family, job.tx
        rep = comm.replicated_sharding()
        sharded = NamedSharding(comm.mesh, P(comm.dp_axes))
        ring = int(job.traffic["batch_ring"])
        with job.spans.span("bench.setup.state"):
            # on the device, from the seed, in one jitted call each
            self.params = jax.jit(fam.init_params,
                                  out_shardings=rep)(job.param_key)
            self.opt_state = jax.jit(tx.init,
                                     out_shardings=rep)(self.params)
            make = jax.jit(fam.make_batch, static_argnums=1,
                           out_shardings=sharded)
            self.batches = [make(job.batch_key(i), job.global_seqs)
                            for i in range(ring)]
        step = make_dp_train_step(comm, fam.loss_fn, tx)
        with job.spans.span("bench.setup.compile"):
            self.compiled = step.lower(self.params, self.opt_state,
                                       self.batches[0]).compile()

    def warmup(self):
        """(losses of the warm-up steps, index of the next step)"""
        n = int(self.job.traffic["warmup_steps"])
        return [float(self.step(i)) for i in range(n)], n

    def step(self, i: int):
        with self.job.spans.span("bench.fused_step"):
            self.params, self.opt_state, loss = self.compiled(
                self.params, self.opt_state,
                self.batches[i % len(self.batches)])
        return loss

    def hlo_texts(self) -> list:
        return [self.compiled.as_text()]

    def checks(self) -> dict:
        n = self.job.chips
        return {"batch_spans_devices":
                checks.spans_all_devices(self.batches, n),
                "state_spans_devices":
                checks.spans_all_devices((self.params, self.opt_state), n),
                "memory_even": checks.memory_even(jax.devices())}

    def free(self) -> None:
        self.params = self.opt_state = self.batches = self.compiled = None
