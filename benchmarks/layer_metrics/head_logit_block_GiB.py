"""GiB of float32 logits the blocked head holds at a time: gauge
``head.logit_block_bytes`` (block rows x table rows x 4), set beside
``head.logit_blocks`` while ``models/gpt.py`` ``blocked_token_nll`` is
traced, as ``bps.metrics_snapshot()`` reads it after the window.  What a
later PR that fuses or re-blocks the head is read against; the whole
``[tokens, vocabulary]`` square would be ``blocks`` times as much (on the
``info`` line)."""

UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "models (head + loss)"
MOVES = "tokens_per_s_per_chip"


def read(run):
    gauges = run.snap1.get("gauges", {})
    block = gauges.get("head.logit_block_bytes")
    if block is None:
        return None
    run.info["head.logit_blocks"] = gauges.get("head.logit_blocks")
    return block / 2 ** 30
