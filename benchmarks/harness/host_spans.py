"""Where the chip's idle time goes, by the program's own host spans.

    python3 benchmarks/harness/host_spans.py <trace dir | file.xplane.pb> [--prefix bps.]

For a ``--trace 1 --keep-trace`` run of a cell (the trace stays under
``.bench_out/trace/<cell>``).  ``xplane.load`` keeps only the
benchmark's own ``bench.*`` spans, which name no phase inside the
program; this reads the host events of ANY prefix (default ``bps.``, the
engine-mode step's phases, ``byteps_tpu/common/tracing.py`` ``phase``)
WITH the thread line each lies on, and splits the first chip's idle
time inside the traced window by the innermost such span open on each
thread.  Threads run at the same time, so every thread accounts for the
whole idle time on its own: the thread whose WORKING spans cover most of
it is the one the chip waits for.  ``run.py`` does not call this; the
numbers go to PERF.md by hand.

Like ``xplane.py``, the arithmetic works on plain ``(name, start_ns,
end_ns)`` tuples and is testable without a trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, Iterable, List

if __name__ == "__main__":          # run as a script: find harness/
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness import intervals as iv  # noqa: E402
from harness import xplane  # noqa: E402

Event = xplane.Event
NO_SPAN = "(no span)"


def load_host(path: str, prefix: str) -> Dict[str, List[Event]]:
    """Host events whose name starts with ``prefix``, by thread line.
    The profile names a line after the thread's OS name, which threads
    share (``python``), so a line's key is ``<plane>#<index>:<name>``."""
    from jax.profiler import ProfileData
    out: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE_RE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            events = [(ev.name, float(ev.start_ns),
                       float(ev.start_ns + ev.duration_ns))
                      for ev in line.events if ev.name.startswith(prefix)]
            if events:
                out[f"{plane.name}#{i}:{line.name}"] = events
    return out


def innermost_segments(events: Iterable[Event]) -> List[Event]:
    """One thread's (properly nested) spans cut into disjoint pieces,
    each named after the innermost span open over it, in time order."""
    out: List[Event] = []
    stack: List[Event] = []

    def emit(name: str, s: float, e: float) -> None:
        if e > s:
            out.append((name, s, e))

    cur = 0.0
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            emit(top[0], cur, top[2])
            cur = top[2]
        if stack:
            emit(stack[-1][0], cur, s)
        cur = s
        stack.append((name, s, e))
    while stack:
        top = stack.pop()
        emit(top[0], cur, top[2])
        cur = max(cur, top[2])
    return out


def idle_by_span(segments: List[Event], gaps: List[iv.Interval]
                 ) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` (sorted, disjoint) under each segment
    name; what no segment covers goes to ``(no span)``."""
    acc: Dict[str, float] = defaultdict(float)
    j = 0
    for name, s, e in segments:
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            acc[name] += min(e, gaps[k][1]) - max(s, gaps[k][0])
            k += 1
    acc[NO_SPAN] = iv.total(gaps) - sum(acc.values())
    return dict(acc)


def span_stats(events: Iterable[Event], lo: float, hi: float
               ) -> Dict[str, dict]:
    """Count, total milliseconds and mean microseconds of the spans of
    each name that START inside [lo, hi)."""
    acc: Dict[str, list] = defaultdict(list)
    for name, s, e in events:
        if lo <= s < hi:
            acc[name].append(e - s)
    return {n: {"count": len(d), "total_ms": sum(d) / 1e6,
                "mean_us": sum(d) / len(d) / 1e3}
            for n, d in sorted(acc.items())}


def report(trace: xplane.Trace, host: Dict[str, List[Event]]) -> dict:
    """The first chip's idle seconds inside the traced window, per host
    thread of ``host`` (``load_host``) by innermost span, next to the
    benchmark's own ``bench.*`` attribution (``xplane.idle_gaps``)."""
    lo, hi = xplane.window(trace)
    ids = trace.device_ids
    if not ids:
        raise ValueError("trace holds no /device:TPU:<i> plane")
    gaps = iv.gaps([(s, e) for _, s, e in trace.ops[ids[0]]], lo, hi)
    threads = {}
    for key, events in host.items():
        idle = idle_by_span(innermost_segments(events), gaps)
        threads[key] = {
            "idle_s": {n: t / 1e9 for n, t in sorted(
                idle.items(), key=lambda kv: -kv[1])},
            "spans": span_stats(events, lo, hi)}
    return {"window_s": (hi - lo) / 1e9, "idle_s": iv.total(gaps) / 1e9,
            "bench_idle_gaps": xplane.idle_gaps(trace),
            "threads": threads}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--prefix", default="bps.")
    args = ap.parse_args(argv)
    path = (xplane.find_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    print(json.dumps(report(xplane.load(path),
                            load_host(path, args.prefix)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
