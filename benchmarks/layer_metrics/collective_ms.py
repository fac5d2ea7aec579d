"""Device milliseconds per step in which an all-reduce, reduce-scatter,
all-gather, all-to-all or collective-permute was in flight (union per
chip, averaged over chips); 0 on one chip."""

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "comm.collectives (in-graph)"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if run.reduced is None:
        return None
    return run.reduced["collective_s"] * 1e3
