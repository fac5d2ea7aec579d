"""Mean over the window's steps of ``thread_cpu.dispatcher``: the
dispatcher thread's WHOLE CPU over the step, read from its own clock at the
step's two boundaries — its ``plan`` / ``dispatch`` / ``compile`` phases
(``engine_dispatch_ms`` is their wall) and the pop between them, which is
in no span.  Far under the wall: a launch waits — for the interpreter lock
the caller and the syncer hold, or inside the runtime.  0 where the engine
saw no step; nothing where the program (or the platform) gives no
per-thread clock."""

from harness.step_cpu import window_mean

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_mean(run, lambda s: s["thread_cpu"]["dispatcher"])
