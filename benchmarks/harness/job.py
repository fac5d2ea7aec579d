"""The measured window: a closed loop of training steps.

One training process issues its next step when an earlier one has
completed.  With ``blocking=False`` (``--trace 0``) the loop keeps a
bounded run-ahead, as a training script does: it blocks on the loss of
step i - run_ahead, keeps losses on the device until the window is over,
and ends the window with ``block_until_ready`` on the last step.  With
``blocking=True`` (``--trace 1``) every step is waited for, which is
what the step-time percentiles need.
"""

from __future__ import annotations

import contextlib
import faulthandler
import os
import sys
import threading
import time
from collections import defaultdict
from typing import Callable, Optional


class Watchdog:
    """Fails a wedged step by name instead of hanging (after
    chip_smoke.Watchdog): past its budget, dump every thread's stack to
    stderr and exit non-zero WITHOUT a result line."""

    def __init__(self):
        self._lock = threading.Lock()
        self._what: Optional[str] = None
        self._deadline = 0.0
        threading.Thread(target=self._run, name="bench-watchdog",
                         daemon=True).start()

    def arm(self, what: str, budget_s: float) -> None:
        with self._lock:
            self._what = what
            self._deadline = time.monotonic() + budget_s

    def disarm(self) -> None:
        with self._lock:
            self._what = None

    def _run(self) -> None:
        while True:
            time.sleep(1.0)
            with self._lock:
                what, late = self._what, time.monotonic() > self._deadline
            if what is not None and late:
                print(f"benchmark: watchdog: {what} exceeded its budget",
                      file=sys.stderr, flush=True)
                faulthandler.dump_traceback(file=sys.stderr)
                os._exit(3)


class Spans:
    """The benchmark's own host spans.  Each is also a
    ``jax.profiler.TraceAnnotation`` so that a traced run can name what
    the host was doing in every idle gap of the device."""

    def __init__(self):
        self.seconds = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name].append(time.perf_counter() - t0)

    def mark(self) -> dict:
        return {k: len(v) for k, v in self.seconds.items()}

    def since(self, mark: dict, name: str) -> list:
        return self.seconds.get(name, [])[mark.get(name, 0):]


class Window:
    """What the loop saw.  ``losses`` stay device arrays until read."""

    def __init__(self):
        self.attempted = 0
        self.completed = 0
        self.failed = 0
        self.errors = []
        self.losses = []
        self.step_s = []          # blocking runs only, untraced steps
        self.traced_step_s = []   # blocking steps under the profiler
        self.traced_steps = 0     # steps inside the profiler's window
        self.step_end_s = []      # since t_first, when each iteration ended
        self.t_first = self.t_last = 0.0

    @property
    def elapsed_s(self) -> float:
        return self.t_last - self.t_first


def run_window(step: Callable[[int], object], first_index: int,
               seconds: float, blocking: bool, run_ahead: int,
               dog: Watchdog, step_budget_s: float, spans: Spans,
               tracer=None) -> Window:
    """Issue steps for ``seconds``; ``step(i)`` returns the step's loss
    (a device array, possibly not yet computed).  ``tracer`` (a traced
    run only) has ``start()`` / ``want_more(n_steps)`` / ``stop()``: the
    profiler covers the first few steps, the rest run untraced."""
    import jax
    w = Window()
    tracing = tracer is not None
    if tracing:
        tracer.start()
    w.t_first = time.perf_counter()
    deadline = w.t_first + seconds
    i = first_index
    while time.perf_counter() < deadline:
        w.attempted += 1
        dog.arm(f"step {i}", step_budget_s)
        t0 = time.perf_counter()
        try:
            loss = step(i)
            w.losses.append(loss)
            with spans.span("bench.block"):
                if blocking:
                    jax.block_until_ready(loss)
                elif len(w.losses) > run_ahead:
                    jax.block_until_ready(w.losses[-1 - run_ahead])
        except Exception as e:  # noqa: BLE001 — a failed step, counted
            w.failed += 1
            w.errors.append(f"step {i}: {type(e).__name__}: {e}"[:400])
            break               # donated state is gone: the job is over
        finally:
            dog.disarm()
        now = time.perf_counter()
        w.step_end_s.append(now - w.t_first)
        if blocking:
            (w.traced_step_s if tracing else w.step_s).append(now - t0)
        i += 1
        if tracing:
            w.traced_steps += 1
            if not tracer.want_more(w.traced_steps):
                tracer.stop()
                tracing = False
    if tracing:
        tracer.stop()
    dog.arm("end of window", step_budget_s)
    try:
        jax.block_until_ready(w.losses)
    except Exception as e:  # noqa: BLE001
        w.failed += 1
        w.errors.append(f"drain: {type(e).__name__}: {e}"[:400])
    dog.disarm()
    w.t_last = time.perf_counter()
    w.completed = len(w.losses) if not w.errors else max(
        0, len(w.losses) - 1)
    return w
