"""``harness/host_spans.py``: the arithmetic on made-up spans, and the
loader and report on a trace made here, on the CPU, with spans on two
threads (a CPU trace has no ``/device:TPU`` plane, so the chip's ops are
put in by hand around the real host events)."""

import threading

import pytest

from harness import host_spans as hs
from harness import xplane


def test_innermost_segments_cut_nested_spans():
    events = [("outer", 0, 100), ("a", 10, 30), ("a.in", 15, 20),
              ("b", 30, 50), ("later", 120, 130)]
    assert hs.innermost_segments(events) == [
        ("outer", 0, 10), ("a", 10, 15), ("a.in", 15, 20), ("a", 20, 30),
        ("b", 30, 50), ("outer", 50, 100), ("later", 120, 130)]
    assert hs.innermost_segments([]) == []


def test_idle_by_span_splits_gaps_exactly():
    segments = hs.innermost_segments(
        [("outer", 0, 100), ("a", 10, 30), ("b", 30, 50)])
    gaps = [(5, 12), (25, 40), (90, 110)]
    idle = hs.idle_by_span(segments, gaps)
    assert idle == {"outer": 5 + 10, "a": 2 + 5, "b": 10,
                    hs.NO_SPAN: 10}
    assert sum(idle.values()) == 7 + 15 + 20
    assert hs.idle_by_span([], gaps) == {hs.NO_SPAN: 42}


def test_span_stats_counts_spans_that_start_in_the_window():
    stats = hs.span_stats([("a", 0, 2000), ("a", 5000, 9000),
                           ("b", 20000, 21000)], lo=0, hi=10000)
    assert stats == {"a": {"count": 2, "total_ms": 0.006, "mean_us": 3.0}}


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import time

    import jax
    from jax.profiler import TraceAnnotation
    trace_dir = str(tmp_path_factory.mktemp("host_spans"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)

    def worker():
        for _ in range(3):
            with TraceAnnotation("bps.engine.dispatch", step=1):
                time.sleep(0.002)

    with TraceAnnotation("bench.traced_window"):
        t = threading.Thread(target=worker)
        t.start()
        with TraceAnnotation("bps.push_pull", step=1):
            with TraceAnnotation("bps.engine.wait", step=1):
                t.join(timeout=30)
        with TraceAnnotation("other.span"):
            pass
    jax.profiler.stop_trace()
    assert not t.is_alive()
    return xplane.find_xplane(trace_dir)


def test_load_host_keeps_the_prefix_and_the_thread(cpu_trace):
    host = hs.load_host(cpu_trace, "bps.")
    assert len(host) == 2                         # two threads, two lines
    names = sorted(sorted({n for n, _, _ in evs}) for evs in host.values())
    assert names == [["bps.engine.dispatch"],
                     ["bps.engine.wait", "bps.push_pull"]]
    assert all(e > s for evs in host.values() for _, s, e in evs)
    assert hs.load_host(cpu_trace, "nothing.") == {}


def test_report_names_each_threads_share_of_the_idle_time(cpu_trace):
    trace = xplane.load(cpu_trace)                # bench.* spans, no chip
    with pytest.raises(ValueError, match="no /device:TPU"):
        hs.report(trace, hs.load_host(cpu_trace, "bps."))
    lo, hi = xplane.window(trace)
    # a chip busy for the first tenth of the window, idle after it
    trace.ops[0] = [("fusion.1", lo, lo + (hi - lo) / 10)]
    rep = hs.report(trace, hs.load_host(cpu_trace, "bps."))
    assert rep["idle_s"] == pytest.approx(0.9 * rep["window_s"])
    caller, = [t for t in rep["threads"].values()
               if "bps.push_pull" in t["spans"]]
    worker, = [t for t in rep["threads"].values()
               if "bps.engine.dispatch" in t["spans"]]
    assert worker["spans"]["bps.engine.dispatch"]["count"] == 3
    assert worker["spans"]["bps.engine.dispatch"]["mean_us"] >= 2000
    # the caller sat in wait (the innermost span), never in push_pull
    assert caller["idle_s"].get("bps.push_pull", 0.0) < 1e-3
    assert caller["idle_s"]["bps.engine.wait"] >= 0.004
    for t in (caller, worker):                    # each thread, all of it
        assert sum(t["idle_s"].values()) == pytest.approx(rep["idle_s"])
