"""Max over chips of ``peak_bytes_in_use + peak_bytes_reserved`` from
``memory_stats()`` right after the window (before the reference runs):
live arrays plus the scratch XLA programs reserve — what decides the
batch a user can fit.  Read by the benchmark from the runtime."""

UNIT = "GiB"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2.0 ** 30
