"""1 - (union of device-op intervals) / (traced window), averaged over
chips.  Collectives count as busy."""

UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if run.reduced is None or run.reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.reduced["busy_s"] / run.reduced["window_s"])
