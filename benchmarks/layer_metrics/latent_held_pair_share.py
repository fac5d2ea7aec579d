"""Share of a step's token-expert pairs that fall on the routed experts
this chip holds, under sigmoid scores over 512 experts, the zero selection
bias and top-22: gauge ``moe.held_pair_share`` of one seeded batch,
published and read as ``moe_held_pair_share`` does (rank 0's shard of the
run's batch 0 under the seeded initial parameters, after the window; every
``E`` block, the module's too).  It is the live share of each block's
``22 N`` pair rows and the number that explains a seed: ``held / routed``
(8 / 512 = 0.0156) under a balanced router, whatever the random router
favours at initialisation."""

from harness import spec

UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return spec.load_module("layer_metrics", "moe_held_pair_share").read(run)
