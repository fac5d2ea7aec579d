"""``route_select_ms`` (PR 41, ``benchmarks/layer_metrics/``): the entry is
found by name, matches its reader file and names the seven MoE cells (the
sixth, ``ling3_flash.fused_1c``, appended by PR 43, the seventh,
``qwen3_next_80b.fused_1c``, by PR 46); the
reader sums the Mosaic kernels under the stage ``bps.moe.route`` of a
made-up trace and counts their calls a step — and gives nothing, without
raising, for a program with no kernel under the stage (the parent commit's)
or a run without a trace.  Here and not under ``benchmarks/tests``: ISSUE
41 allowed the benchmark ONE new file."""

import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MOE_CELLS = ["olmoe_1b_7b.fused_1c", "mellum2_12b.fused_1c",
             "zaya1_8b.fused_1c", "glm47_flash.fused_1c",
             "nemotron3_super.fused_1c", "ling3_flash.fused_1c",
             "qwen3_next_80b.fused_1c"]


def test_route_select_entry_and_reader(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmarks"))
    from harness import spec, xplane
    reader = spec.load_module("layer_metrics", "route_select_ms")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == "route_select_ms"]
    assert entry == [{
        "name": "route_select_ms", "unit": reader.UNIT,
        "better": reader.BETTER, "source": reader.SOURCE,
        "layer": reader.LAYER, "moves": reader.MOVES,
        "workloads": MOE_CELLS}]
    assert entry[0]["layer"] in {m["layer"] for m in bench["per_layer"]
                                 if m is not entry[0]}
    for cell in (w["name"] for w in bench["workloads"]):
        names = {m["name"] for m in spec.metrics_for(bench, "per_layer",
                                                     cell)}
        assert ("route_select_ms" in names) == (cell in MOE_CELLS)

    # two traced steps of a program with the kernel in two blocks (forward +
    # recomputed): 4 calls a step at 0.5 ms = 2.0 ms; the grouped matmuls
    # and a row kernel of another stage are not in it
    step = "jit(step)/jvp(h3)/moe/"
    mosaic = {
        "select.1": step + "bps.moe.route/jit(_select_call)/bps_moe_select"
                           "/pallas_call",
        "select.2": "jit(step)/transpose(jvp(h3))/rematted_computation/moe/"
                    "bps.moe.route/jit(_select_call)/bps_moe_select/"
                    "pallas_call",
        "select.3": "jit(f)/jvp(bps.moe.route)/jit(_select_call)/"
                    "bps_moe_select/pallas_call",
        "gmm.1": step + "bps.moe.experts/jit(gmm)/pallas_call",
        "spread.1": step + "bps.moe.dispatch/jit(_spread_rows)/"
                           "bps_moe_spread/pallas_call"}
    trace = xplane.Trace()
    t = 0.0
    for _ in range(2):
        for name in ("select.1", "gmm.1", "select.2", "spread.1",
                     "select.3", "select.1", "sort.7"):
            trace.ops[0].append((name, t, t + 0.5e6))
            t += 1e6

    def run(trace, mosaic):
        return types.SimpleNamespace(
            trace=trace, mosaic=mosaic, info={},
            window=types.SimpleNamespace(traced_steps=2))
    with_kernel = run(trace, mosaic)
    assert reader.read(with_kernel) == pytest.approx(2.0)
    assert with_kernel.info == {"route_select_calls_per_step": 4.0}
    by_a_sort = {k: v for k, v in mosaic.items() if "select" not in k}
    for nothing in (run(trace, by_a_sort), run(None, mosaic)):
        assert reader.read(nothing) is None and nothing.info == {}
