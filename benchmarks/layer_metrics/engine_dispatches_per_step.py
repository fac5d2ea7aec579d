"""Dispatch units the engine issued per step: observations of histogram
``engine.dispatch_unit_width`` (one per unit, core/engine.py) as a delta
over the window, from ``bps.metrics_snapshot()``.  0 proves a path
bypasses the engine."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


NAME = "engine.dispatch_unit_width"


def _count(snap):
    return sum(sum(buckets.values())
               for series, buckets in snap.get("histograms", {}).items()
               if series == NAME or series.startswith(NAME + "{"))


def read(run):
    if run.window.completed == 0:
        return None
    return (_count(run.snap1) - _count(run.snap0)) / run.window.completed
