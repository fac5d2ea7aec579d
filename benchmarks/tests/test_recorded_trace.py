"""The reduction from .xplane.pb to numbers, on recorded traces.

Every ``fixtures/<cell>.xplane.pb`` is the first steps of a traced chip
run of that cell, trimmed to what the reader needs (each chip's ``XLA
Ops``, ``Async XLA Ops`` and ``XLA Modules`` lines and the host's
``bench.*`` spans; stats dropped, event names cut to the instruction's
name).  ``<cell>.expected.json`` holds what the reduction gave when the
trace was recorded, and where it was recorded.  A later PR pins another
cell's trace by adding the two files.
"""

import glob
import json
import os

import pytest

from harness import spec, xplane

FIXTURES = os.path.join(spec.BENCH_DIR, "fixtures")
CELLS = sorted(os.path.basename(p)[:-len(".expected.json")]
               for p in glob.glob(os.path.join(FIXTURES, "*.expected.json")))


@pytest.fixture(scope="module", params=CELLS)
def recorded(request):
    with open(os.path.join(FIXTURES, request.param + ".expected.json")) as f:
        expected = json.load(f)
    return xplane.load(os.path.join(FIXTURES, request.param + ".xplane.pb")), \
        expected


def test_there_is_a_recorded_trace():
    assert CELLS


def test_trace_has_the_cells_chips_and_the_benchmarks_spans(recorded):
    trace, expected = recorded
    assert trace.device_ids == list(range(expected["chips"]))
    names = {n for n, _, _ in trace.host}
    assert {"bench.traced_window", "bench.block"} <= names
    for d in trace.device_ids:
        assert len(trace.modules[d]) == expected["steps"]


def test_reduction_reproduces_the_recorded_numbers(recorded):
    trace, expected = recorded
    r = xplane.reduce(trace, expected["steps"])
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert idle == pytest.approx(expected["device_idle_pct"], rel=1e-9)
    for key, name in (("collective_s", "collective_ms"),
                      ("collective_exposed_s", "collective_exposed_ms"),
                      ("step_device_s", "step_device_ms")):
        assert r[key] * 1e3 == pytest.approx(expected[name], rel=1e-9)
    if "flash_ms" in expected:
        names = {n for d in trace.device_ids for n, _, _ in trace.ops[d]
                 if n.startswith("attn.")}
        assert len(names) == expected["flash_calls_per_step"]
        assert xplane.op_seconds(trace, names, expected["steps"]) * 1e3 == \
            pytest.approx(expected["flash_ms"], rel=1e-9)


def test_recorded_numbers_make_sense_by_hand(recorded):
    trace, expected = recorded
    # a step is one module per chip, and its duration is the mean of them
    durations = [e - s for d in trace.device_ids
                 for _, s, e in trace.modules[d]]
    assert sum(durations) / len(durations) / 1e6 == pytest.approx(
        expected["step_device_ms"], rel=1e-6)
    assert 0 <= expected["device_idle_pct"] < 20
    assert 0 <= expected["collective_exposed_ms"] <= expected["collective_ms"]
    assert (expected["collective_ms"] > 0) == (expected["chips"] > 1)
    assert expected["collective_ms"] < expected["step_device_ms"]
    assert xplane.top_device_ops(trace)[0][1] > 0
    assert xplane.idle_gaps(trace)


def test_a_traced_line_holds_each_per_layer_metric_of_the_cell(recorded):
    """The driver refuses a traced line that lacks a metric BENCHMARK.json
    lists for the cell (PR 22's first check: ``flash_roofline`` carried no
    ``workloads`` list, and its reader rightly found nothing in BERT)."""
    import types

    from harness.job import Spans, Window
    from harness.peaks import peaks_for

    trace, expected = recorded
    bench = spec.load_benchmark()
    found = spec.resolve(bench, expected["workload"])
    family = spec.load_module("families", found["config"]["family"]).build(
        found["config"], found["traffic"])
    per = int(found["traffic"]["seqs_per_chip"])
    window = Window()
    window.completed = window.traced_steps = expected["steps"]
    window.traced_step_s = ([expected["step_device_ms"] / 1e3]
                            * expected["steps"])
    ops = {n for d in trace.device_ids for n, _, _ in trace.ops[d]}
    run = types.SimpleNamespace(
        job=types.SimpleNamespace(
            tokens_per_step_per_chip=per * family.tokens_per_seq),
        family=family, window=window, spans=Spans(), span_mark={},
        snap0={}, snap1={}, jax_compiles_in_window=0, engine_steps={},
        engine_step_mark=-1, peaks=peaks_for("TPU v5 lite"),
        kernel_work=family.kernel_work(per), trace=trace,
        reduced=xplane.reduce(trace, expected["steps"]),
        # the fixture's event names are cut to the instruction's name
        mosaic={n: "jit(step)/attn/pallas_call" for n in ops
                if n.startswith("attn.")},
        info={})
    listed = {m["name"] for m in spec.metrics_for(bench, "per_layer",
                                                  expected["workload"])}
    given = {m["name"] for m in bench["per_layer"]
             if spec.load_module("layer_metrics", m["name"]).read(run)
             is not None}
    assert given == listed
