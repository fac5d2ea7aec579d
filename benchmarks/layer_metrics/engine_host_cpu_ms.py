"""Mean over the window's steps of all CPU the engine spends a step, in or
out of a span: ``thread_cpu.dispatcher + thread_cpu.syncer`` (those threads'
WHOLE CPU over the step, from their own clocks at its two boundaries) plus
the caller's CPU inside ``bps.push_pull`` (``push_pull_cpu_ms``: enqueue,
submit and what the wait burns).  Against ``pushpull_ms``: near it, about
one thread runs at any moment — one interpreter lock is saturated and only
less work under it helps; well under it, the threads wait on something
else.  0 where the engine saw no step; nothing where the program (or the
platform) gives no per-thread clock."""

from harness.step_cpu import window_mean

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    def engine_cpu_ms(step):
        t = step["thread_cpu"]
        return t["dispatcher"] + t["syncer"] + step["push_pull_cpu_ms"]
    return window_mean(run, engine_cpu_ms)
