"""Median over the window's steps of ``update_ms − push_pull_ms −
attrib.tx_update``: the adapter's self time — what
``DistributedOptimizer.update()`` (span ``bps.adapter.update``) spends
outside its two children ``bps.push_pull`` and ``bps.adapter.tx_update``:
flattening the gradient tree, naming its leaves, unflattening the result,
the accumulation lock.  Three spans of the program, subtracted.  0 where the
engine saw no step; nothing where the program lacks a span."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "byteps_tpu.jax adapter"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_median(
        run, lambda s: (s["update_ms"] - s["push_pull_ms"]
                        - s["attrib"]["tx_update"]))
