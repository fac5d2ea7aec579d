"""The command itself: the rehearsal's final line, and no result without a
TPU.  Each case is one fresh process, as the driver runs it."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

RUN = os.path.join(spec.BENCH_DIR, "run.py")
DEVICE_SOURCES = ("device_trace", "host_clock", "program_span")
BENCH = spec.load_benchmark()


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, RUN, *args], cwd=spec.CHECKOUT,
                          env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("cell,trace", [
    ("bert_large.fused_1c", 0), ("bert_large.engine_1c", 1),
    ("gpt2_medium.fused_1c", 1), ("bert_large.fused_4c", 1)])
def test_rehearsal_prints_the_contract_line_and_no_device_metric(cell, trace):
    p = _run("--workload", cell, "--seed", "3", "--seconds", "1",
             "--trace", str(trace), "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    chips = spec.resolve(BENCH, cell)["cell"]["chips"]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["device"]["rehearsal"] is True
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name, m in line["metrics"].items():
        assert by_name[name]["source"] not in DEVICE_SOURCES, name
        assert m["unit"] == by_name[name]["unit"]
    if trace:
        d = line["metrics"]["engine_dispatches_per_step"]["value"]
        engine = cell == "bert_large.engine_1c"
        assert (d > 0) == engine
        # the engine compiles one program per dispatch-unit width, and
        # which widths occur depends on timing: on a loaded CPU one in
        # ten rehearsals still meets a new width after the warm-up
        compiles = line["metrics"]["compiles_in_window"]["value"]
        assert compiles >= 0 if engine else compiles == 0


def test_no_tpu_and_no_rehearsal_exits_nonzero_with_empty_stdout():
    p = _run("--workload", "bert_large.fused_1c", "--seed", "0",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_exits_nonzero_with_empty_stdout():
    p = _run("--workload", "nope", "--rehearsal")
    assert p.returncode != 0 and p.stdout == ""
