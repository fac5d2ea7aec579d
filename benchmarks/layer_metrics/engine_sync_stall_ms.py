"""Median over the window's steps of ``StepStats.sync_stall_ms`` (host time
the engine's syncer spent blocked on device completion), sampled from
``bps.metrics_snapshot()['step']`` after every step; 0 where the engine
saw no step."""

import numpy as np

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    steps = [s for n, s in run.engine_steps.items()
             if n > run.engine_step_mark]
    if not steps:
        return 0.0
    return float(np.median([s["sync_stall_ms"] for s in steps]))
