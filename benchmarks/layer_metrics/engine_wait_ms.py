"""Median over the window's steps of ``attrib.wait``: the caller thread BLOCKED
on the tree's handles (span ``bps.engine.wait``) while dispatcher and syncer
work.  0 where the engine saw no step."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_median(run, lambda s: s["attrib"]["wait"])
