"""Median over the window's steps of ``StepStats.push_pull_ms``: the caller
thread's wall inside ``byteps_tpu.jax.push_pull`` (span ``bps.push_pull``:
first leaf enqueued to last handle returned) — the engine's part of the
step, the wall the three threads' phases are read against.
``adapter_update_ms`` minus this is the optax update's dispatch.  0 where the
engine saw no step."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "byteps_tpu.jax adapter"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_median(run, lambda s: s["push_pull_ms"])
