"""``setup_s`` split by the program's own start-up record: the stamps of
``bps.metrics_snapshot()["startup"]`` (one ``time.monotonic`` clock:
``import_begin`` / ``import_end``, first and last statement of
``byteps_tpu/__init__.py``; ``init_begin`` / ``init_end``, the first
``bps.init()``; ``now``, the moment of the snapshot) and the ``compile.*``
counters (``common/telemetry.py``: JAX's own trace / lower / backend /
cache-retrieval durations, a union of each thread's intervals) as they
stand in ``run.snap0``, which ``run.py`` takes at the moment set-up ends.

The eight durations add up to ``run.setup_s`` by construction: ``now``
places the program's clock against the benchmark's, so what lies before
the program's first statement is ``setup_s − (now − import_begin)``, and
what follows ``bps.init()`` and is not compile work is the rest.  A
program without the record (the parent of the PR that adds it) gives
nothing: every reader returns ``None``."""

from __future__ import annotations

from typing import Optional

STAMPS = ("import_begin", "import_end", "init_begin", "init_end", "now")


def counter(snap: dict, name: str) -> float:
    return float(snap.get("counters", {}).get(name, 0.0))


def parts(run) -> Optional[dict]:
    """``{metric name: seconds}`` of the eight durations, or None."""
    rec = run.snap0.get("startup") or {}
    if any(rec.get(k) is None for k in STAMPS):
        return None
    trace = counter(run.snap0, "compile.trace_ms") / 1e3
    lower = counter(run.snap0, "compile.lower_ms") / 1e3
    build = (counter(run.snap0, "compile.backend_ms")
             + counter(run.snap0, "compile.cache_retrieval_ms")) / 1e3
    return {
        "setup_pre_import_s":
            run.setup_s - (rec["now"] - rec["import_begin"]),
        "setup_import_s": rec["import_end"] - rec["import_begin"],
        "setup_import_to_init_s": rec["init_begin"] - rec["import_end"],
        "setup_init_s": rec["init_end"] - rec["init_begin"],
        "setup_trace_s": trace,
        "setup_lower_s": lower,
        "setup_compile_s": build,
        "setup_rest_s": rec["now"] - rec["init_end"] - trace - lower - build,
    }


def part(run, name: str) -> Optional[float]:
    found = parts(run)
    return None if found is None else found[name]
