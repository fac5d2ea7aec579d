"""The part of ``collective_ms`` during which no compute op ran on the same
chip: communication the step could not hide."""

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "comm.collectives (in-graph)"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if run.reduced is None:
        return None
    return run.reduced["collective_exposed_s"] * 1e3
