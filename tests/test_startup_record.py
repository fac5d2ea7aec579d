"""ISSUE 48 — the program's own start-up record: the stamps of
``metrics_snapshot()["startup"]``, JAX's compile durations as the
``compile.*`` counters (a UNION of each thread's intervals, never a sum),
and the nine ``setup_*`` readers under ``benchmarks/layer_metrics`` that
split ``setup_s`` with them.  Light: no model, no profiler session."""

import json
import os
import threading
import types

import jax
import jax.numpy as jnp
import pytest

import byteps_tpu as bps
from byteps_tpu.common import telemetry
from byteps_tpu.common.config import Config, set_config
from byteps_tpu.common.telemetry import CompileSpans, counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

@pytest.fixture(autouse=True)
def _fresh_spans(monkeypatch):
    """Each test's events meet an empty record (the process's own holds
    whatever this thread compiled a moment ago)."""
    monkeypatch.setattr(telemetry, "_compile_spans", CompileSpans())


# (events as (seconds, now) in order of arrival, what each may claim)
UNION_CASES = {
    # an inner jit traced inside an outer one reports first
    "nested": ([(2.0, 5.0), (4.0, 6.0)], [2.0, 2.0]),
    # three children in turn, then the event that holds them all
    "siblings_then_parent": ([(1.0, 2.0), (1.0, 4.0), (0.5, 5.0),
                              (5.0, 6.0)], [1.0, 1.0, 0.5, 2.5]),
    "back_to_back": ([(1.0, 1.0), (1.0, 2.0), (1.0, 3.0)],
                     [1.0, 1.0, 1.0]),
    # the outer's own length is shorter than what lies inside its end
    "inner_longer_than_outer": ([(5.0, 10.0), (3.0, 10.001)],
                                [5.0, 0.001]),
    # an event that starts inside an earlier one takes what sticks out
    "partial_overlap": ([(2.0, 4.0), (3.0, 6.0)], [2.0, 2.0]),
    "apart": ([(1.0, 2.0), (1.0, 10.0)], [1.0, 1.0]),
    "no_length": ([(0.0, 1.0), (-1.0, 2.0), (1.0, 3.0)], [0.0, 0.0, 1.0]),
}


@pytest.mark.parametrize("case", sorted(UNION_CASES))
def test_a_thread_claims_the_union_of_its_intervals(case):
    events, want = UNION_CASES[case]
    spans = CompileSpans()
    got = [spans.claim(seconds, now) for seconds, now in events]
    assert got == pytest.approx(want, abs=1e-9)
    first = min(now - max(0.0, s) for s, now in events)
    assert sum(got) <= events[-1][1] - first + 1e-9


def test_two_threads_do_not_share_a_record():
    spans, got = CompileSpans(), {}

    def work(name):
        got[name] = [spans.claim(2.0, 5.0), spans.claim(4.0, 6.0)]

    threads = [threading.Thread(target=work, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == {"a": [2.0, 2.0], "b": [2.0, 2.0]}


def test_a_long_record_is_folded_and_still_a_union(monkeypatch):
    """Past the cap the older half is ONE span that remembers its cover:
    an event that holds everything still gets only what was uncovered."""
    monkeypatch.setattr(CompileSpans, "_CAP", 8)
    spans = CompileSpans()
    n = 50
    for i in range(n):                     # 1 s of work every 2 s
        assert spans.claim(1.0, 2.0 * i + 1.0) == pytest.approx(1.0)
    assert len(spans._local.spans) <= 8
    assert spans.claim(2.0 * n, 2.0 * n) == pytest.approx(float(n))


def _compile_counters():
    return {k: v for k, v in counters.snapshot().items()
            if k.startswith("compile.")}


@pytest.mark.parametrize("event,name", sorted(
    telemetry.COMPILE_DURATION_COUNTERS.items()))
def test_each_duration_event_feeds_its_counter(event, name):
    telemetry._on_compile_duration(event, 0.25)
    telemetry._on_compile_duration("/jax/some/other_duration", 9.0)
    got = _compile_counters()
    assert got.pop(name) == pytest.approx(250.0, rel=1e-3)
    assert got == ({"compile.programs": 1} if event == BACKEND else {})


@pytest.mark.parametrize("event,name", sorted(
    telemetry.COMPILE_EVENT_COUNTERS.items()))
def test_each_cache_event_feeds_its_counter(event, name):
    telemetry._on_compile_event(event)
    telemetry._on_compile_event("/jax/compilation_cache/tasks_using_cache")
    assert _compile_counters() == {name: 1}


def test_backend_time_is_what_lies_outside_the_retrieval():
    """JAX 0.9.0: ``backend_compile_duration`` wraps the cache's
    retrieval, which reports first — the counters stay disjoint."""
    telemetry._on_compile_duration(RETRIEVAL, 0.2)
    telemetry._on_compile_duration(BACKEND, 0.25)
    got = _compile_counters()
    assert got["compile.cache_retrieval_ms"] == pytest.approx(200, rel=1e-2)
    assert got["compile.backend_ms"] == pytest.approx(50, rel=5e-2)


def test_a_jit_compiled_twice_is_counted_once():
    telemetry.listen_to_compiles()
    telemetry.listen_to_compiles()         # registers nothing again
    from jax._src import monitoring
    assert monitoring.get_event_duration_listeners().count(
        telemetry._on_compile_duration) == 1
    assert monitoring.get_event_listeners().count(
        telemetry._on_compile_event) == 1

    @jax.jit
    def f(x):
        return jax.jit(lambda y: jnp.sin(y) * 2)(x) + 1

    x = jnp.arange(4.0)
    before = _compile_counters()
    jax.block_until_ready(f(x))
    once = _compile_counters()
    jax.block_until_ready(f(x))
    assert _compile_counters() == once
    moved = {k: once[k] - before.get(k, 0) for k in once}
    assert moved["compile.programs"] == 1
    assert moved["compile.trace_ms"] > 0 and moved["compile.lower_ms"] > 0
    assert moved["compile.backend_ms"] > 0


def test_with_telemetry_off_the_listeners_add_nothing():
    set_config(Config(telemetry_on=False))
    telemetry.listen_to_compiles()
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)))
    telemetry._on_compile_duration(TRACE, 1.0)
    telemetry._on_compile_event("/jax/compilation_cache/cache_hits")
    assert _compile_counters() == {}


STAMPS = ("import_begin", "import_end", "init_begin", "init_end", "now")


def test_the_stamps_are_in_order_and_survive_a_resume():
    bps.init()
    try:
        light = bps.metrics_snapshot(light=True)
        first = bps.metrics_snapshot()["startup"]
        assert [first[k] for k in STAMPS] == sorted(first[k] for k in STAMPS)
        assert first["import_end"] > first["import_begin"]
        parts = first["init_parts_ms"]
        assert set(parts) == {"mesh", "engine", "services"}
        assert sum(parts.values()) <= (
            first["init_end"] - first["init_begin"]) * 1e3 + 1e-6
        bps.suspend()
        bps.resume()
        again = bps.metrics_snapshot()["startup"]
        assert {k: again[k] for k in STAMPS[:4]} == {
            k: first[k] for k in STAMPS[:4]}
        assert again["init_parts_ms"] == parts
        assert again["now"] >= first["now"]
        # the light form is built every step: it stays as it was
        assert set(light) == {"ts", "pid", "rank", "epoch", "counters",
                              "gauges", "speed_mbps", "scheduler",
                              "sched_pending", "bytes_in_flight", "step"}
        json.dumps(again)
    finally:
        bps.shutdown()


# ---- the nine readers, on a made-up run ------------------------------------

READERS = {
    "setup_pre_import_s": 3.5, "setup_import_s": 2.25,
    "setup_import_to_init_s": 6.0, "setup_init_s": 2.0,
    "setup_trace_s": 4.0, "setup_lower_s": 7.5, "setup_compile_s": 1.75,
    "setup_rest_s": 13.0, "setup_cache_misses": 3.0}


def _run(startup=True):
    t = 1000.0                      # the program's clock, another epoch
    snap0 = {"counters": {
        "compile.trace_ms": 4000.0, "compile.lower_ms": 7500.0,
        "compile.backend_ms": 250.0, "compile.cache_retrieval_ms": 1500.0,
        "compile.programs": 10, "compile.cache_hits": 7,
        "compile.cache_misses": 3}}
    if startup:
        snap0["startup"] = {
            "import_begin": t, "import_end": t + 2.25,
            "init_begin": t + 8.25, "init_end": t + 10.25,
            "init_parts_ms": {"mesh": 1500.0, "engine": 300.0,
                              "services": 100.0},
            "now": t + 36.5}
    snap1 = {"counters": dict(snap0["counters"])}
    return types.SimpleNamespace(snap0=snap0, snap1=snap1, setup_s=40.0,
                                 info={})


def _reader(monkeypatch, name):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmarks"))
    from harness import spec
    return spec.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_on_a_made_up_run(monkeypatch, name):
    reader = _reader(monkeypatch, name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": reader.UNIT,
                     "better": "lower", "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": "setup_s"}
    assert reader.SOURCE == ("program_counter" if name == "setup_cache_misses"
                             else "program_span")
    assert reader.read(_run()) == pytest.approx(READERS[name], abs=1e-9)
    # the parent has no record: nothing read, nothing raised
    run = _run(startup=False)
    assert reader.read(run) is None and run.info == {}
    half = _run()
    half.snap0["startup"]["init_begin"] = None     # init never ran
    assert reader.read(half) is None


def test_the_eight_durations_add_up_to_setup_s(monkeypatch):
    run = _run()
    total = sum(_reader(monkeypatch, n).read(run) for n in READERS
                if n != "setup_cache_misses")
    assert total == pytest.approx(run.setup_s, abs=1e-9)
    _reader(monkeypatch, "setup_cache_misses").read(run)
    assert run.info["init_parts_ms"] == {"mesh": 1500.0, "engine": 300.0,
                                         "services": 100.0}
    assert run.info["compile_counters"] == {
        "compile.backend_ms": 250.0, "compile.cache_retrieval_ms": 1500.0,
        "compile.programs": 10.0, "compile.cache_hits": 7.0,
        "compile.cache_misses": 3.0}
    assert set(run.info["compile_counters_in_window"].values()) == {0.0}
