"""Share of a step's tokens whose ONE expert is among those this chip
holds: gauge ``moe.held_pair_share`` of one seeded batch, published and
read as ``moe_held_pair_share`` does (rank 0's shard of the run's batch 0
under the seeded initial parameters, after the window).  At top-1 a pair
is a token, so this is the live share of each layer's pair rows and the
number that explains a seed: ``held / routed`` (0.5) under a balanced
router, whatever the random MLP router favours at initialisation."""

from harness import spec

UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return spec.load_module("layer_metrics", "moe_held_pair_share").read(run)
