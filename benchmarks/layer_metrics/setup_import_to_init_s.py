"""Seconds of ``setup_s`` between ``import byteps_tpu`` and ``bps.init()``:
stamps ``init_begin − import_end`` — the caller's own; in ``run.py`` the
cache switch, ``jax.devices()`` (the TPU runtime's start) and the harness
imports.  A program without the record gives nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "process start-up"
MOVES = "setup_s"


def read(run):
    return startup.part(run, "setup_import_to_init_s")
