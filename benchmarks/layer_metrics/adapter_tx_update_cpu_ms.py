"""Mean over the window's steps of ``attrib_cpu.tx_update``: of
``adapter_tx_update_ms``, the milliseconds the caller thread was RUNNING
(its CPU clock, from the span's own enter/exit pair).  Near the wall: the
update's dispatch is work on the caller's thread — argument and buffer
handling over every leaf of gradients, state and parameters; far under it:
the caller is blocked inside the runtime.  0 where the engine saw no step;
nothing where the program reads no second clock."""

from harness.step_cpu import window_mean

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "byteps_tpu.jax adapter"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_mean(run, lambda s: s["attrib_cpu"]["tx_update"])
