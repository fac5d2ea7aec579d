"""The engine's own per-step record (``StepStats`` dicts, sampled by
``paths/engine.py`` from ``bps.metrics_snapshot()['step']`` after every
step) reduced to one number per metric."""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np


def window_median(run, value: Callable[[dict], float]) -> Optional[float]:
    """Median of ``value(step)`` over the window's engine steps; 0.0
    where the engine saw no step (every fused cell: the proof of
    bypass).  ``value`` indexes the dict: where a step lacks the field —
    a program older than the counter, as the parent of the PR that adds
    one is — the metric has nothing to read and is ``None`` (left out of
    the line, named on stderr), never a 0."""
    steps = [s for n, s in run.engine_steps.items()
             if n > run.engine_step_mark]
    if not steps:
        return 0.0
    try:
        return float(np.median([value(s) for s in steps]))
    except KeyError:
        return None
