"""Median over the window's steps of ``attrib.tx_update``: the caller
thread's wall inside ``DistributedOptimizer``'s own ``tx.update(reduced,
state, params)`` (span ``bps.adapter.tx_update``: after ``bps.push_pull``,
inside ``bps.adapter.update``) — the largest part of what
``adapter_update_ms − pushpull_ms`` timed from outside.  0 where the engine
saw no step; nothing where the program has no such span."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "byteps_tpu.jax adapter"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_median(run, lambda s: s["attrib"]["tx_update"])
