"""``moe_window_trips`` (PR 40): the entry is found by name and matches its
reader file; the reader publishes through ``moe_held_pair_share``'s and
reads the gauge ``moe.window_trips`` — and gives nothing, without raising,
for a program whose layer sets none (the parent commit's, under this PR's
benchmark files) or a family that holds no experts' share."""

import json
import os
import types

import numpy as np

from harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME, CELL = "moe_window_trips", "nemotron3_super.fused_1c"
READER = spec.load_module("layer_metrics", NAME)


def test_the_entry_is_found_by_name_and_matches_its_file():
    found = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert len(found) == 1 and BENCH["per_layer"][-1] is found[0]
    m = found[0]
    assert m == {"name": NAME, "unit": READER.UNIT, "better": READER.BETTER,
                 "source": READER.SOURCE, "layer": READER.LAYER,
                 "moves": READER.MOVES, "workloads": [CELL]}
    assert m["layer"] in {e["layer"] for e in BENCH["per_layer"][:-1]}
    for cell in (w["name"] for w in BENCH["workloads"]):
        reported = {e["name"] for e in spec.metrics_for(
            BENCH, "per_layer", cell)}
        assert (NAME in reported) == (cell == CELL)


def test_it_reads_the_gauge_the_program_sets():
    """A thin share (2 of 128 experts held, 32 768 pair rows, windows of
    1 024): 2 100 live rows in the worst layer -> 3 windows."""
    from byteps_tpu.parallel.expert import publish_moe_stats
    counts = np.zeros((2, 128), np.int64)
    counts[0, [0, 9, 10, 127]] = 5000, 600, 300, 32768 - 5900
    counts[1, [0, 9, 10, 127]] = 100, 2000, 100, 32768 - 2200
    publish_moe_stats(counts, held=(9, 2))
    run = types.SimpleNamespace(info={"moe.held_pair_share": 3000 / 65536})
    assert READER.read(run) == 3.0
    assert run.info["moe.visited_row_share"] == 4 * 1024 / 65536


def test_it_reads_nothing_where_the_program_sets_no_such_gauge(monkeypatch):
    import byteps_tpu as bps
    # a family that holds no share of the experts
    assert READER.read(types.SimpleNamespace(
        info={}, family=types.SimpleNamespace())) is None
    # a program whose layer works on whole arrays: share, but no trips
    monkeypatch.setattr(bps, "metrics_snapshot", lambda: {"gauges": {
        "moe.held_pair_share": 0.25, "moe.visited_row_share": 0.27}})
    run = types.SimpleNamespace(info={"moe.held_pair_share": 0.25})
    assert READER.read(run) is None
    assert "moe.visited_row_share" not in run.info
