"""Device milliseconds per step inside XLA programs: durations of the
``XLA Modules`` events of the traced steps, averaged over chips."""

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "parallel.data_parallel step program"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if run.reduced is None:
        return None
    return run.reduced["step_device_s"] * 1e3
