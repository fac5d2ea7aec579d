"""Seconds of ``setup_s`` after ``bps.init()`` that are not compile work:
``(startup.now − startup.init_end) − trace − lower − compile`` — the init
programs and the warm-up steps executing, executables loading, the caller's
Python; in a ``--trace 1`` run also ``runner.hlo_texts()``
(``compiled.as_text()``), which ``run.py`` calls before it ends set-up.  A
program without the record gives nothing."""

from harness import startup

UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "job loop"
MOVES = "setup_s"


def read(run):
    return startup.part(run, "setup_rest_s")
