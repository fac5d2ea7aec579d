"""The seven readers of the engine-mode step's second clock and of the
adapter's remainder (ISSUE 33) on made-up runs, and their BENCHMARK.json
entries — looked up by name — against the files."""

import types

import pytest

from harness import spec

CELL = "bert_large.engine_1c"
# name -> (layer, source)
NEW = {
    "adapter_tx_update_ms": ("byteps_tpu.jax adapter", "program_span"),
    "adapter_tx_update_cpu_ms": ("byteps_tpu.jax adapter", "program_counter"),
    "adapter_self_ms": ("byteps_tpu.jax adapter", "program_span"),
    "engine_enqueue_cpu_ms": ("core.engine + common.scheduler",
                              "program_counter"),
    "engine_dispatch_cpu_ms": ("core.engine + common.scheduler",
                               "program_counter"),
    "engine_assemble_cpu_ms": ("core.engine + common.scheduler",
                               "program_counter"),
    "engine_host_cpu_ms": ("core.engine + common.scheduler",
                           "program_counter"),
}


def _step(n, k=1.0, **less):
    attrib = {"enqueue": 10 * k, "submit": 2 * k, "wait": 30 * k,
              "plan": 1 * k, "dispatch": 20 * k, "assemble": 5 * k,
              "sync": 7 * k, "queue": 900 * k, "tx_update": 50 * k,
              "other": 0.0}
    # outside a profiler session only the once-a-step phases read the
    # thread's clock
    cpu = {"wait": 0.5 * k, "tx_update": 40 * k}
    return {"step": n, "push_pull_ms": 43 * k, "push_pull_cpu_ms": 7.5 * k,
            "update_ms": 100 * k, "sync_stall_ms": 7 * k, "attrib": attrib,
            "attrib_cpu": cpu,
            "thread_cpu": {"caller": 70 * k, "dispatcher": 9 * k,
                           "syncer": 4 * k}, **less}


def _run(steps, mark=2):
    return types.SimpleNamespace(engine_steps={s["step"]: s for s in steps},
                                 engine_step_mark=mark)


WANT = {"adapter_tx_update_ms": 50.0, "adapter_tx_update_cpu_ms": 40.0,
        "adapter_self_ms": 100.0 - 43.0 - 50.0,
        "engine_enqueue_cpu_ms": 7.5 - 0.5, "engine_dispatch_cpu_ms": 9.0,
        "engine_assemble_cpu_ms": 4.0,
        "engine_host_cpu_ms": 9.0 + 4.0 + 7.5}
# the wall readers take the window's median, the CPU readers its mean
# (harness/step_cpu.py: the host's CPU clocks tick in 10 ms)
MEDIAN = {"adapter_tx_update_ms", "adapter_self_ms"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_reduces_the_windows_steps(name):
    read = spec.load_module("layer_metrics", name).read
    # steps 1-2 are warm-up (at or under the mark) and ten times slower
    steps = [_step(1, 10), _step(2, 10), _step(3, 1), _step(4, 2), _step(5, 6)]
    scale = 2 if name in MEDIAN else 3            # median / mean of 1, 2, 6
    assert read(_run(steps)) == pytest.approx(scale * WANT[name])
    assert read(_run([])) == 0.0                  # a fused cell
    assert read(_run(steps, mark=5)) == 0.0       # no step in the window


def test_a_mean_resolves_what_the_10ms_ticks_hide():
    """Per-step readings on the benchmark host are multiples of 10 ms: the
    mean over the window recovers a value between them, a median cannot."""
    read = spec.load_module("layer_metrics", "engine_dispatch_cpu_ms").read
    ticks = [20.0, 30.0, 20.0, 20.0, 30.0, 20.0, 30.0, 20.0, 20.0, 30.0]
    steps = [_step(3 + i, thread_cpu={"dispatcher": t, "syncer": 0.0})
             for i, t in enumerate(ticks)]
    assert read(_run(steps)) == pytest.approx(24.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_parents_step_reads_nothing_not_zero(name):
    """The parent's StepStats (every field PR 32 had, none of ISSUE 33's):
    the metric is left out of the line, never reported as 0."""
    read = spec.load_module("layer_metrics", name).read
    old = _step(3)
    for key in ("attrib_cpu", "push_pull_cpu_ms", "thread_cpu", "update_ms"):
        del old[key]
    del old["attrib"]["tx_update"]
    assert read(_run([old])) is None


def test_a_platform_without_thread_clocks_reads_nothing():
    """``thread_cpu`` leaves out what it cannot read: the metric that
    needs the engine threads' clocks is then left out too."""
    read = spec.load_module("layer_metrics", "engine_host_cpu_ms").read
    assert read(_run([_step(3, thread_cpu={"caller": 70.0})])) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_matches_its_file(name):
    by_name = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    m, reader = by_name[name], spec.load_module("layer_metrics", name)
    layer, source = NEW[name]
    assert m == {"name": name, "unit": "ms/step", "better": "lower",
                 "source": source, "layer": layer,
                 "moves": "tokens_per_s_per_chip", "workloads": [CELL]}
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
            reader.MOVES) == (m["unit"], m["better"], m["source"],
                              m["layer"], m["moves"])


def test_only_the_engine_cell_lists_them():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        names = {m["name"] for m in spec.metrics_for(
            bench, "per_layer", w["name"])}
        assert (set(NEW) <= names) == (w["name"] == CELL), w["name"]
        assert (set(NEW) & names) in (set(), set(NEW))
