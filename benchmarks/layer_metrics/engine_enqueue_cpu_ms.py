"""Mean over the window's steps of ``push_pull_cpu_ms − attrib_cpu.wait``:
the caller thread's CPU inside ``bps.push_pull`` outside its wait — of
``engine_enqueue_ms`` (its ``enqueue`` + ``submit`` phases, which are all
the caller does there), the milliseconds it was RUNNING.  The difference is
the caller holding ``bps.engine.enqueue`` open and not running: waiting for
the interpreter lock, or inside a launch that released it.  (Two reads of
the thread's clock a step, not two a tensor: PERF.md §6, PR 33.)  0 where
the engine saw no step; nothing where the program reads no second clock."""

from harness.step_cpu import window_mean

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_mean(
        run, lambda s: s["push_pull_cpu_ms"] - s["attrib_cpu"]["wait"])
