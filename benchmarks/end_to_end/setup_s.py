"""Process start to the first measured step: imports, ``bps.init``,
parameters made on the device from the seed, compile or cache load of
this cell's programs, warm-up (the engine's planner included)."""

UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
