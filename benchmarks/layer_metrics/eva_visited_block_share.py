"""Share of the score sub-blocks of BOTH key sets of EVA attention that the
flash kernels visit: gauge ``eva.visited_block_share``, set while
``byteps_tpu/ops/eva_attention.py`` ``eva_attention`` is traced from
``block_schedule`` — the ``T / window`` causal window calls (10 of 16
sub-blocks of 512 x 512 at a window of 2 048) and the staircase call over
the ``T / chunk`` summaries (sub-blocks past a row block's step are skipped
by trip count), visited / total.  ``eva.summary_keys`` and
``eva.saved_lse_bytes`` go on the ``info`` line.  A program without the
gauge gives nothing."""

UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    gauges = run.snap1.get("gauges", {})
    share = gauges.get("eva.visited_block_share")
    if share is None:
        return None
    for name in ("eva.summary_keys", "eva.saved_lse_bytes"):
        run.info[name] = gauges.get(name)
    return share
