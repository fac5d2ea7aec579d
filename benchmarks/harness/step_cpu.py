"""The engine's per-step CPU readings (``StepStats.attrib_cpu``,
``push_pull_cpu_ms``, ``thread_cpu``: thread CPU clocks) reduced to one
number per metric.

A MEAN over the window's steps, where ``step_stats.window_median`` takes
the median of the wall readings: the v5e benchmark host's kernel (gVisor)
advances a thread's CPU clock in ticks of 10 ms, so one step's reading is a
multiple of 10 ms — 20.0 or 30.0 for a true 24 — and a median of such
values is one of them.  Ticks are charged where they fall, so the sum over
the window's ~100 steps (2 000 ticks) is right to a few per cent, and the
mean is that sum a step."""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np


def window_mean(run, value: Callable[[dict], float]) -> Optional[float]:
    """Mean of ``value(step)`` over the window's engine steps; 0.0 where
    the engine saw no step (every fused cell), ``None`` where a step
    lacks the field (an older program, or a platform that gives no
    per-thread clock): the metric is then left out of the line, never
    reported as 0 — as ``window_median``."""
    steps = [s for n, s in run.engine_steps.items()
             if n > run.engine_step_mark]
    if not steps:
        return 0.0
    try:
        return float(np.mean([value(s) for s in steps]))
    except KeyError:
        return None
