"""Seconds of ``setup_s`` JAX spent tracing Python to jaxprs: counter
``compile.trace_ms`` at ``snap0`` ÷ 1 000 (``jaxpr_trace_duration``, inner
and outer ``jit`` as a union of the thread's intervals, less any lowering
or compile that ran inside).  A program without the record gives
nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "compile (jit + engine AOT)"
MOVES = "setup_s"


def read(run):
    return startup.part(run, "setup_trace_s")
