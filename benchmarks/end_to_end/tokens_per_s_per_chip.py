"""Tokens of the steps completed inside the window over (end of the last
completed step - start of the first), per chip.  Whole-window, not
median-based: a stall or a compile inside the window counts."""

UNIT = "tokens/s/chip"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    w = run.window
    if w.completed == 0 or w.elapsed_s <= 0:
        return None
    return w.completed * run.job.tokens_per_step_per_chip / w.elapsed_s
