"""Host milliseconds per step inside ``DistributedOptimizer.update()`` (the
benchmark's own span ``bench.opt_update`` around the call); 0 where the
path never calls the adapter."""

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "byteps_tpu.jax adapter"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = run.spans.since(run.span_mark, "bench.opt_update")
    return 1e3 * sum(s) / len(s) if s else 0.0
