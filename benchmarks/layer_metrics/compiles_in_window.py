"""Programs compiled (or loaded as new executables) inside the window:
delta of counter ``engine.compile_cache_miss`` plus JAX's own
backend-compile events seen by a ``jax.monitoring`` listener.  Should
be 0: everything was warmed up during set-up."""

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "compile (jit + engine AOT)"
MOVES = "tokens_per_s_per_chip"


NAME = "engine.compile_cache_miss"


def read(run):
    c0 = run.snap0.get("counters", {}).get(NAME, 0)
    c1 = run.snap1.get("counters", {}).get(NAME, 0)
    return float(c1 - c0 + run.jax_compiles_in_window)
