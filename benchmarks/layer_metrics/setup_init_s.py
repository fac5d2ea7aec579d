"""Seconds of ``setup_s`` inside the first ``bps.init()``: stamps
``init_end − init_begin`` (the ``tracing.phase`` ``bps.init``).  Its parts
go on the ``info`` line (``init_parts_ms``: the phases ``bps.init.mesh``,
``bps.init.engine``, ``bps.init.services``).  A program without the record
gives nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "process start-up"
MOVES = "setup_s"


def read(run):
    value = startup.part(run, "setup_init_s")
    if value is not None:
        run.info["init_parts_ms"] = run.snap0["startup"].get("init_parts_ms")
    return value
