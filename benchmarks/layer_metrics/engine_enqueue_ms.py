"""Median over the window's steps of ``attrib.enqueue + attrib.submit``: the
caller thread WORKING inside ``Engine.push_pull_async`` — validation,
planning and staging of every leaf (span ``bps.engine.enqueue``) and the
``ChunkTask`` loop into the scheduler (``bps.engine.submit``).  0 where the
engine saw no step."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_median(
        run, lambda s: s["attrib"]["enqueue"] + s["attrib"]["submit"])
