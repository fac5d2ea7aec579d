"""Median over the window's steps of ``attrib.assemble``: the syncer thread
WORKING — retirement of each unit after its device block, assembly and
callbacks (span ``bps.engine.assemble``); its blocked half is
``engine_sync_stall_ms``.  0 where the engine saw no step."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return window_median(run, lambda s: s["attrib"]["assemble"])
