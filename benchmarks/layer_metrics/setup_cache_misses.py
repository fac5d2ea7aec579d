"""Programs set-up had to compile because the persistent cache did not
hold them: counter ``compile.cache_misses`` at ``snap0``
(``/jax/compilation_cache/cache_misses``: an executable compiled and
written).  0 on a warm run; what tells a first run's ``setup_s`` from a
later one's.  The ``compile.*`` counters' change over the window goes on
the ``info`` line (``compile_counters_in_window``: all 0, or the program
has caught a recompile).  A program without the record gives nothing."""

from harness import startup

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "compile (jit + engine AOT)"
MOVES = "setup_s"


def read(run):
    if startup.parts(run) is None:
        return None
    moved = {n: v - startup.counter(run.snap0, n)
             for n, v in run.snap1.get("counters", {}).items()
             if n.startswith("compile.")}
    run.info["compile_counters_in_window"] = moved
    return startup.counter(run.snap0, "compile.cache_misses")
