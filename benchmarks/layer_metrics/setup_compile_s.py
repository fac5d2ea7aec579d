"""Seconds of ``setup_s`` inside JAX's backend-compile calls: counters
``compile.backend_ms + compile.cache_retrieval_ms`` at ``snap0`` ÷ 1 000 —
XLA compiling (a cold cache) plus the persistent cache handing executables
back (a warm one).  The two apart, the programs, the cache's hits and
misses go on the ``info`` line (``compile_counters``).  A program without
the record gives nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "compile (jit + engine AOT)"
MOVES = "setup_s"

NAMES = ("compile.backend_ms", "compile.cache_retrieval_ms",
         "compile.programs", "compile.cache_hits", "compile.cache_misses")


def read(run):
    value = startup.part(run, "setup_compile_s")
    if value is not None:
        run.info["compile_counters"] = {
            n: startup.counter(run.snap0, n) for n in NAMES}
    return value
