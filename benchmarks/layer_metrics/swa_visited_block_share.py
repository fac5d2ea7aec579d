"""Share of the score square's sub-blocks the sliding-window layers' flash
kernels visit: the program's own gauge ``flash.visited_block_share.window``
(``ops.flash_attention.block_schedule`` visited / total, set when a
windowed ``flash_attention`` call is traced), as ``bps.metrics_snapshot()``
reads it after the window.  45 / 256 = 0.176 at 8192 positions under a
window of 1024 when the kernels skip by trip count; 136 / 256 = 0.53 would
be the mask alone."""

UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"

NAME = "flash.visited_block_share.window"


def read(run):
    return run.snap1.get("gauges", {}).get(NAME)
