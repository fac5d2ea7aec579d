"""BENCHMARK.json against the contract's rules and the files it names."""

import os

import pytest

from harness import spec

BENCH = spec.load_benchmark()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
PER_LAYER = {m["name"]: m for m in BENCH["per_layer"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert os.path.getsize(os.path.join(spec.CHECKOUT,
                                        "BENCHMARK.json")) <= 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in BENCH["workloads"]} == \
        {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_existing_files(cell):
    found = spec.resolve(BENCH, cell)
    assert found["cell"]["chips"] in (1, 4)
    assert len(found["cell"]["why"]) <= 200
    family = found["config"]["family"]
    path = found["traffic"]["path"]
    for kind, name in (("families", family), ("paths", path)):
        assert os.path.exists(os.path.join(spec.BENCH_DIR, kind,
                                           name + ".py"))
    # the toy sizes the rehearsal runs are data too
    assert "rehearsal" in found["config"] and "rehearsal" in found["traffic"]


def test_config_entries_match_their_files():
    for c in BENCH["configs"]:
        doc = spec.load_json(os.path.join(spec.CHECKOUT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:      # never a width
            assert not key.endswith(("_dim", "_rank"))
            assert key not in ("hidden_size", "intermediate_size",
                               "n_embd", "n_inner", "num_attention_heads",
                               "n_head")


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_names_units_and_readers(m):
    assert spec.NAME_RE.match(m["name"]), m["name"]
    assert spec.UNIT_RE.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in spec.SOURCES
    per_layer = m["name"] in PER_LAYER
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(m) <= allowed
    reader = spec.load_module("layer_metrics" if per_layer else "end_to_end",
                              m["name"])
    assert reader.UNIT == m["unit"] and reader.BETTER == m["better"]
    assert reader.SOURCE == m["source"] and callable(reader.read)
    if per_layer:
        assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_every_name_passes_the_character_rules():
    names = [w[k] for w in BENCH["workloads"]
             for k in ("name", "config", "traffic")]
    names += [c["name"] for c in BENCH["configs"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert spec.NAME_RE.match(n), n
    for root, _dirs, files in os.walk(spec.BENCH_DIR):
        if "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), spec.CHECKOUT)
            assert all(c.isalnum() or c in "_.-/" for c in rel), rel


def test_moves_names_an_end_to_end_metric_of_a_cell_that_reports_it():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    for cell in CELLS:
        e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.metrics_for(BENCH, "per_layer", cell)
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (cell, m["name"])


def test_command_stays_inside_paths():
    cmd = BENCH["command"]
    assert len(cmd) <= 32
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(spec.CHECKOUT, word)):
            assert word.startswith(tuple(p + "/" for p in BENCH["paths"]))


def test_a_config_that_states_what_the_model_cannot_compute_is_refused():
    doc = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "bert_large.json"))
    spec.fixed(doc, hidden_act="gelu_tanh", layer_norm_eps=1e-6)
    with pytest.raises(spec.SpecError, match="hidden_act"):
        spec.fixed(dict(doc, hidden_act="gelu"), hidden_act="gelu_tanh")


def test_every_traffic_file_is_some_cells_traffic():
    used = {w["traffic"] + ".json" for w in BENCH["workloads"]}
    assert set(os.listdir(os.path.join(spec.BENCH_DIR, "traffic"))) == used
