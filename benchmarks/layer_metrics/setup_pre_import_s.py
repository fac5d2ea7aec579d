"""Seconds of ``setup_s`` before the program's first statement:
``run.setup_s − (startup.now − startup.import_begin)`` — the interpreter's
own start, the caller's imports, ``import jax``; everything between the
benchmark's first clock read and the first line of
``byteps_tpu/__init__.py``, which the program cannot see and the stamp
``now`` places (``harness/startup.py``).  A program without the record
gives nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "process start-up"
MOVES = "setup_s"


def read(run):
    return startup.part(run, "setup_pre_import_s")
