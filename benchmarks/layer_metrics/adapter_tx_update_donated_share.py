"""Share of the optax update's outputs that were written into a buffer
the call itself consumed: gauge ``adapter.tx_update_donated_share``, set by
``DistributedOptimizer`` after the first call of each compiled signature of
its own update program (donated input leaves the call deleted ÷ output
leaves), as ``bps.metrics_snapshot()`` reads it after the window.  1.0 =
the reduced gradients and the state carried every output and the step
allocated none; 0 = the backend or the shapes gave the donation nothing to
alias.  Nothing where the program sets no such gauge (a fused cell, a
program older than the gauge)."""

UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "byteps_tpu.jax adapter"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return run.snap1.get("gauges", {}).get("adapter.tx_update_donated_share")
