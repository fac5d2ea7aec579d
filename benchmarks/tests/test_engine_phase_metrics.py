"""The five readers of the engine's phases (ISSUE 23) on made-up runs,
and their BENCHMARK.json entries against the files."""

import types

import pytest

from harness import spec

NEW = {"pushpull_ms": "byteps_tpu.jax adapter",
       "engine_enqueue_ms": "core.engine + common.scheduler",
       "engine_wait_ms": "core.engine + common.scheduler",
       "engine_dispatch_ms": "core.engine + common.scheduler",
       "engine_assemble_ms": "core.engine + common.scheduler"}


def _step(n, k=1.0, **less):
    attrib = {"enqueue": 10 * k, "submit": 2 * k, "wait": 30 * k,
              "plan": 1 * k, "dispatch": 20 * k, "assemble": 5 * k,
              "sync": 7 * k, "queue": 900 * k, "other": 0.0}
    attrib.update(less.pop("attrib", {}))
    return {"step": n, "push_pull_ms": 43 * k, "sync_stall_ms": 7 * k,
            "attrib": attrib, **less}


def _run(steps, mark=2):
    return types.SimpleNamespace(engine_steps={s["step"]: s for s in steps},
                                 engine_step_mark=mark)


WANT = {"pushpull_ms": 43.0, "engine_enqueue_ms": 12.0,
        "engine_wait_ms": 30.0, "engine_dispatch_ms": 21.0,
        "engine_assemble_ms": 5.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_takes_the_median_of_the_windows_steps(name):
    read = spec.load_module("layer_metrics", name).read
    # steps 1-2 are warm-up (at or under the mark) and ten times slower
    steps = [_step(1, 10), _step(2, 10), _step(3, 1), _step(4, 2), _step(5, 3)]
    assert read(_run(steps)) == pytest.approx(2 * WANT[name])
    assert read(_run([])) == 0.0                  # a fused cell
    assert read(_run(steps, mark=5)) == 0.0       # no step in the window


def test_a_compiling_unit_counts_as_dispatcher_work():
    read = spec.load_module("layer_metrics", "engine_dispatch_ms").read
    assert read(_run([_step(3, attrib={"compile": 100.0})])) == 121.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_step_without_the_field_reads_nothing_not_zero(name):
    """An older program's StepStats (the parent of the PR that adds the
    counter): the metric is left out of the line, never reported as 0."""
    read = spec.load_module("layer_metrics", name).read
    old = {"step": 3, "sync_stall_ms": 7.0,
           "attrib": {"enqueue": 10.0, "dispatch": 20.0, "sync": 7.0}}
    assert read(_run([old])) is None


def test_the_new_entries_end_the_list_and_match_their_files():
    bench = spec.load_benchmark()
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == [
        "pushpull_ms", "engine_enqueue_ms", "engine_wait_ms",
        "engine_dispatch_ms", "engine_assemble_ms"]
    for m in tail:
        reader = spec.load_module("layer_metrics", m["name"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}                # every cell reports it
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms/step", "lower", "program_span", "tokens_per_s_per_chip")
        assert m["layer"] == NEW[m["name"]] == reader.LAYER
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.MOVES) == (
            m["unit"], m["better"], m["source"], m["moves"])
