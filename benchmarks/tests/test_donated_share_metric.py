"""The reader of ``adapter_tx_update_donated_share`` (ISSUE 34) on recorded
snapshots with and without the gauge, and its BENCHMARK.json entry against
the file."""

import types

import pytest

from harness import spec

NAME = "adapter_tx_update_donated_share"
CELL = "bert_large.engine_1c"
# bps.metrics_snapshot() after a window of engine-mode AdamW steps, cut
# to the gauges; the second as the parent of ISSUE 34 prints it
WITH = {"gauges": {"step.pushes": 28.0, "step.update_ms": 108.4,
                   "adapter.tx_update_donated_share": 1.0}}
WITHOUT = {"gauges": {"step.pushes": 28.0, "step.update_ms": 155.6}}


@pytest.mark.parametrize("snap, want", [
    (WITH, 1.0),
    ({"gauges": {"adapter.tx_update_donated_share": 0.0}}, 0.0),
    (WITHOUT, None),        # the parent: left out of the line, never a 0
    ({}, None),             # telemetry off
], ids=["donated", "nothing_to_alias", "parent", "no_gauges"])
def test_reader_reads_the_gauge_or_nothing(snap, want):
    read = spec.load_module("layer_metrics", NAME).read
    assert read(types.SimpleNamespace(snap1=snap)) == want


def test_the_entry_matches_its_file():
    bench = spec.load_benchmark()
    # by name: later PRs append to the list
    (m,) = [e for e in bench["per_layer"] if e["name"] == NAME]
    reader = spec.load_module("layer_metrics", NAME)
    assert m == {"name": NAME, "unit": "ratio", "better": "higher",
                 "source": "program_counter",
                 "layer": "byteps_tpu.jax adapter",
                 "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
            reader.MOVES) == (m["unit"], m["better"], m["source"],
                              m["layer"], m["moves"])
    for w in bench["workloads"]:
        names = {e["name"] for e in spec.metrics_for(
            bench, "per_layer", w["name"])}
        assert (NAME in names) == (w["name"] == CELL), w["name"]
