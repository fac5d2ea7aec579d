"""Mean over the window's steps of ``thread_cpu.syncer``: the syncer
thread's WHOLE CPU over the step, read from its own clock at the step's two
boundaries — retiring units (``engine_assemble_ms`` is that phase's wall:
unpack launch, callbacks), its queue get and bookkeeping, and whatever its
block in ``bps.engine.sync`` burns (a parked thread: nothing).  0 where the
engine saw no step; nothing where the program (or the platform) gives no
per-thread clock."""

from harness.step_cpu import window_mean

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_mean(run, lambda s: s["thread_cpu"]["syncer"])
