"""Seconds of ``setup_s`` inside ``import byteps_tpu``: stamps
``import_end − import_begin`` of ``metrics_snapshot()["startup"]`` (first
and last statement of ``byteps_tpu/__init__.py``) — the package and what
it pulls in (``core``, ``server/``, ``fault/``, optax, flax), JAX already
imported by the caller.  A program without the record gives nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "process start-up"
MOVES = "setup_s"


def read(run):
    return startup.part(run, "setup_import_s")
