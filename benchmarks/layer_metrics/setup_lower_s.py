"""Seconds of ``setup_s`` JAX spent lowering jaxprs to MLIR modules:
counter ``compile.lower_ms`` at ``snap0`` ÷ 1 000
(``jaxpr_to_mlir_module_duration``) — holds every Pallas kernel's tracing
and lowering to Mosaic, which runs in Python before the compile cache is
asked: a kernel's size shows here.  A program without the record gives
nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "compile (jit + engine AOT)"
MOVES = "setup_s"


def read(run):
    return startup.part(run, "setup_lower_s")
