"""Share of each layer's ``tokens x experts per token`` pair rows that the
held-experts layer's row passes visit: gauge ``moe.visited_row_share``, as
``byteps_tpu.parallel.expert.publish_moe_stats(counts, held=...)`` sets it
beside ``moe.held_pair_share`` from ``row_schedule``'s live chunks — the
live share rounded up to whole chunks at either end of the held experts'
rows; every other chunk is written as zeros and nothing of it is read.
Published by ``moe_held_pair_share``'s reader (after the window, ONE
batch); a program whose layer visits every row sets no such gauge, and
this returns nothing."""

from harness import spec

UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"

NAME = "moe.visited_row_share"


def read(run):
    if spec.load_module("layer_metrics", "moe_held_pair_share").read(
            run) is None:
        return None
    import byteps_tpu as bps
    return bps.metrics_snapshot()["gauges"].get(NAME)
