"""Unit tests for the evidence-tool helpers (tools/): the pure logic the
bench artifacts depend on — core-slice math, pin-spec parsing, quantile
stats — pinned without wall-clock dependence."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools._bench_util import quantile_stats  # noqa: E402
from tools.weak_scaling import _core_slices  # noqa: E402


def test_quantile_stats_median_and_iqr():
    med, iqr = quantile_stats([0.1, 0.2, 0.3, 0.4])
    assert med == 250.0
    assert iqr == [175.0, 325.0]


def test_quantile_stats_single_sample():
    med, iqr = quantile_stats([0.05])
    assert med == 50.0 and iqr == [50.0, 50.0]


def test_core_slices_disjoint_and_capped(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(8)), raising=False)
    # same per-worker budget regardless of group size (cap = max group's
    # share): 1-proc group must NOT get all 8 cores when the cap is 2
    assert _core_slices(1, cores_per_proc=2) == [[0, 1]]
    s4 = _core_slices(4, cores_per_proc=2)
    assert s4 == [[0, 1], [2, 3], [4, 5], [6, 7]]
    flat = [c for s in s4 for c in s]
    assert len(flat) == len(set(flat))          # disjoint
    # infeasible: 4 workers x 3 cores > 8
    assert _core_slices(4, cores_per_proc=3) is None


def test_core_slices_single_core(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: {0}, raising=False)
    assert _core_slices(4) is None
    assert _core_slices(1) == [[0]]


def test_couple_overlap_to_projection():
    import json

    import bench

    line = json.dumps({
        "overlap": {"overlap_fraction": 0.5},
        "scaling": {"analytic_v5e256": {
            "measured_step_ms_per_chip": 60.0, "allreduce_ms": 20.0,
            "efficiency_no_overlap": 0.75}},
    })
    out = json.loads(bench._couple_overlap_to_projection(line))
    an = out["scaling"]["analytic_v5e256"]
    assert an["measured_overlap_fraction"] == 0.5
    assert an["efficiency_at_measured_overlap"] == round(60 / 70, 3)
    # negative measured fraction clamps to the no-overlap end
    line2 = json.dumps({
        "overlap": {"overlap_fraction": -0.2},
        "scaling": {"analytic_v5e256": {
            "measured_step_ms_per_chip": 60.0, "allreduce_ms": 20.0}},
    })
    an2 = json.loads(bench._couple_overlap_to_projection(line2))[
        "scaling"]["analytic_v5e256"]
    assert an2["efficiency_at_measured_overlap"] == 0.75
    # the disjoint-pinned measurement, when present, wins over unpinned
    # (round-5: transport-on-own-cores is the TPU-host-like regime)
    line3 = json.dumps({
        "overlap": {"overlap_fraction": -0.1,
                    "pinned_disjoint": {"overlap_fraction": 0.5}},
        "scaling": {"analytic_v5e256": {
            "measured_step_ms_per_chip": 60.0, "allreduce_ms": 20.0}},
    })
    an3 = json.loads(bench._couple_overlap_to_projection(line3))[
        "scaling"]["analytic_v5e256"]
    assert an3["measured_overlap_fraction"] == 0.5
    # a SKIPPED pinned section must not mask the unpinned fraction
    line4 = json.dumps({
        "overlap": {"overlap_fraction": 0.3,
                    "pinned_disjoint": {"skipped": "1 core"}},
        "scaling": {"analytic_v5e256": {
            "measured_step_ms_per_chip": 60.0, "allreduce_ms": 20.0}},
    })
    an4 = json.loads(bench._couple_overlap_to_projection(line4))[
        "scaling"]["analytic_v5e256"]
    assert an4["measured_overlap_fraction"] == 0.3
    # missing sections pass through untouched
    assert bench._couple_overlap_to_projection("{}") == "{}"


@pytest.mark.parametrize("spec,avail,want", [
    ("off", {0, 1, 2, 3}, None),
    ("none", {0, 1, 2, 3}, None),
    ("1", {0, 1, 2, 3}, [1]),             # bare "1" is core 1, not a flag
    ("0", {0, 1, 2, 3}, [0]),
    ("0-2", {0, 1, 2, 3}, [0, 1, 2]),
    ("0,2", {0, 1, 2, 3}, [0, 2]),
    ("bogus", {0, 1, 2, 3}, None),        # malformed: unpinned, not dead
    ("0-3", {0, 1, 2, 3}, None),          # explicit full set: no-op, no
                                          # stabilization to report
    ("", {0}, None),                      # 1-core default: nothing to pin
    ("", {0, 1, 2, 3}, [1, 2, 3]),        # default: all but core 0
    ("", {0, 1}, None),                   # 2-3 cores: full-set pin is a
                                          # no-op, don't report one
])
def test_pin_cores_spec_parsing(monkeypatch, spec, avail, want):
    from tools import _bench_util

    monkeypatch.setenv("BYTEPS_BENCH_PIN", spec)
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(avail), raising=False)
    pinned = {}
    monkeypatch.setattr(os, "sched_setaffinity",
                        lambda pid, cores: pinned.update(c=sorted(cores)),
                        raising=False)
    got = _bench_util.pin_cores()
    assert got == want
    if want is not None:
        assert pinned["c"] == want          # affinity actually applied


# ---------------------------------------------------------------------------
# bench.py streamed-section protocol: an inner killed at its time limit
# still yields a partial line from the sections it already streamed.
# ---------------------------------------------------------------------------

import json  # noqa: E402

import bench  # noqa: E402


def _section_line(key, value):
    return "BENCH_SECTION " + json.dumps({"key": key, "value": value})


def test_sections_salvage_and_hung_attribution():
    out = "\n".join([
        "BENCH_SECTION_START device",
        _section_line("device", {"device_kind": "TPU v5 lite",
                                 "n_devices": 1, "on_tpu": True}),
        "BENCH_SECTION_START push_pull_gbps",
        _section_line("push_pull_gbps", {"engine_256MB": 9.9}),
        "BENCH_SECTION_START train",  # started, never completed
        "garbage line the parser must skip",
    ])
    sections, hung = bench._sections_from_stdout(out)
    assert sections["push_pull_gbps"] == {"engine_256MB": 9.9}
    assert hung == "train"


def test_sections_salvage_empty_and_malformed():
    assert bench._sections_from_stdout("") == ({}, None)
    sections, hung = bench._sections_from_stdout(
        "BENCH_SECTION not json\nBENCH_SECTION_START flash_attention\n")
    assert sections == {} and hung == "flash_attention"


def test_assemble_partial_without_train_keeps_tpu_identity():
    sections = {
        "device": {"device_kind": "TPU v5 lite", "n_devices": 1,
                   "on_tpu": True},
        "push_pull_gbps": {"engine_256MB": 9.9, "fused_256MB": 34.0},
    }
    result = bench._assemble(sections, note="hung in train")
    assert result["metric"] == "bert_large_mlm_train_throughput_per_chip"
    assert result["value"] == 0.0
    assert result["device"] == "TPU v5 lite"
    assert result["push_pull_gbps"]["engine_256MB"] == 9.9
    assert result["flash_attention"] == {"skipped": "not reached"}
    assert "hung in train" in result["error"]


def test_assemble_train_error_dict_is_not_a_result():
    sections = {
        "device": {"device_kind": "TPU v5 lite", "n_devices": 1,
                   "on_tpu": True},
        "train": {"error": "RuntimeError: chip gone"},
    }
    result = bench._assemble(sections)
    assert result["value"] == 0.0
    assert "chip gone" in result["error"]


def test_is_degraded():
    assert bench._is_degraded({"partial": True, "value": 500.0})
    assert bench._is_degraded({"value": 0.0})
    assert not bench._is_degraded({"value": 500.0})
    assert not bench._is_degraded(None)


def test_assemble_salvage_does_not_write_baseline(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "MEASURED_BASELINE_FILE",
                        str(tmp_path / "BASELINE_MEASURED.json"))
    train = {"on_tpu": True, "per_chip": 100.0, "mfu": 0.5,
             "tokens_per_sec_per_chip": 1e4, "device_kind": "TPU v5 lite",
             "n_devices": 1, "seq_len": 128, "per_dev_batch": 32}
    sections = {"device": {"device_kind": "TPU v5 lite", "n_devices": 1,
                           "on_tpu": True}, "train": train}
    bench._assemble(sections, write_baseline=False)
    assert not (tmp_path / "BASELINE_MEASURED.json").exists()
    bench._assemble(sections)  # the inner's full-run path does write
    assert (tmp_path / "BASELINE_MEASURED.json").exists()


def test_tpu_overlap_section_shape_on_cpu_mesh():
    # The section runs on-TPU in the bench; this pins its structure at CPU
    # scale so API drift can't break the TPU capture right when a green
    # window opens (the fraction itself is jitter on a shared host and is
    # deliberately not asserted).
    import jax
    out = bench._bench_tpu_overlap(jax.devices())
    assert "error" not in out, out
    for key in ("compute_ms", "comm_ms", "serial_ms", "pipelined_ms",
                "overlap_fraction", "grad_mb", "note"):
        assert key in out
    assert out["serial_ms"] > 0 and out["pipelined_ms"] > 0


def test_sections_salvage_progress_lines():
    # A section killed mid-stream: its last PROGRESS value is salvaged and
    # it is still attributed as the hung section; a later full SECTION
    # line for the same key wins over progress.
    out = "\n".join([
        "BENCH_SECTION_START push_pull_gbps",
        "BENCH_SECTION_PROGRESS " + json.dumps(
            {"key": "push_pull_gbps", "value": {"fused_256MB": 34.0}}),
        "BENCH_SECTION_PROGRESS " + json.dumps(
            {"key": "push_pull_gbps",
             "value": {"fused_256MB": 34.0, "engine_device_256MB": 12.0}}),
    ])
    sections, hung = bench._sections_from_stdout(out)
    assert sections["push_pull_gbps"]["engine_device_256MB"] == 12.0
    assert hung == "push_pull_gbps"
    # completed section: full line wins, no hang
    out2 = out + "\nBENCH_SECTION " + json.dumps(
        {"key": "push_pull_gbps", "value": {"fused_256MB": 35.0}})
    sections2, hung2 = bench._sections_from_stdout(out2)
    assert sections2["push_pull_gbps"] == {"fused_256MB": 35.0}
    assert hung2 is None


def test_push_pull_raising_measurement_keeps_partials():
    # Review finding: a measurement that RAISES mid-section must keep the
    # sizes already measured and skip the rest.
    import jax

    # _bench_push_pull imports PushPullEngine per call, so patching the
    # module attribute faults the Nth engine construction for real.
    import byteps_tpu.core.engine as eng_mod
    real_engine = eng_mod.PushPullEngine
    n_made = [0]

    class FlakyEngine(real_engine):
        def __init__(self, *a, **kw):
            n_made[0] += 1
            if n_made[0] >= 2:   # first engine (device path) OK, then die
                raise RuntimeError("chip gone")
            super().__init__(*a, **kw)

    snaps = []
    eng_mod.PushPullEngine = FlakyEngine
    try:
        out = bench._bench_push_pull(jax.devices(), on_tpu=False,
                                     emit=lambda v: snaps.append(v))
    finally:
        eng_mod.PushPullEngine = real_engine
    assert "fused_8MB" in out            # measured before the fault
    assert "engine_device_8MB" in out    # first engine construction OK
    assert "error" in out and "chip gone" in out["error"]
    assert "engine_8MB_credit16MB" not in out   # skipped after the fault
    assert snaps[-1] == out


def _stub_tpu_sections(monkeypatch, tmp_path, order, fail=()):
    """Fake one TPU device and stub every bench section; sections named
    in ``fail`` raise."""
    import jax

    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite (fake)"

    def stub(name, val=None):
        def f(*a, **kw):
            order.append(name)
            if name in fail:
                raise RuntimeError(f"{name} refused by the compiler")
            return val if val is not None else {"ok": name}
        return f

    monkeypatch.setattr(bench, "_bench_push_pull", stub("push_pull_gbps"))
    monkeypatch.setattr(bench, "_bench_tpu_overlap", stub("tpu_overlap"))
    monkeypatch.setattr(bench, "_bench_pallas", stub("onebit_pallas"))
    monkeypatch.setattr(bench, "_bench_flash", stub("flash_attention"))
    monkeypatch.setattr(bench, "_bench_train_step", stub("train", {
        "on_tpu": True, "per_chip": 500.0, "mfu": 0.75,
        "tokens_per_sec_per_chip": 64000.0,
        "device_kind": "TPU v5 lite (fake)", "n_devices": 1,
        "seq_len": 128, "per_dev_batch": 32}))
    monkeypatch.setattr(bench, "_bench_resnet", stub("resnet50"))
    monkeypatch.setattr(bench, "_bench_bf16_fsdp_tp", stub("bf16_fsdp_tp"))
    monkeypatch.setattr(bench, "_bench_bf16_three_d", stub("bf16_three_d"))
    monkeypatch.setattr(bench, "MEASURED_BASELINE_FILE",
                        str(tmp_path / "b.json"))
    monkeypatch.setattr(jax, "devices", lambda: [FakeDev()])
    monkeypatch.delenv("_BPS_BENCH_ONLY", raising=False)


def test_inner_main_tpu_branch_order_and_assembly(monkeypatch, capsys,
                                                  tmp_path):
    # The TPU branch only executes on a chip — exactly when a regression
    # would be found too late.  Stub every section and check dispatch
    # order (cheap evidence before the multi-minute compiles), the
    # emission protocol, and the assembled line.
    order = []
    _stub_tpu_sections(monkeypatch, tmp_path, order)

    assert bench.inner_main() == 0
    out = capsys.readouterr().out
    assert order == ["push_pull_gbps", "tpu_overlap", "onebit_pallas",
                     "flash_attention", "train", "resnet50",
                     "bf16_fsdp_tp", "bf16_three_d"]
    starts = [ln.split()[1] for ln in out.splitlines()
              if ln.startswith("BENCH_SECTION_START")]
    assert starts[0] == "device" and starts[1] == "push_pull_gbps"
    final = json.loads(out.strip().splitlines()[-1])
    assert final["value"] == 500.0
    assert final["tpu_overlap"] == {"ok": "tpu_overlap"}
    assert final["device"] == "TPU v5 lite (fake)"
    assert (tmp_path / "b.json").exists()   # first-green baseline written


def test_inner_main_raised_section_fails_after_the_rest_printed(
        monkeypatch, capsys, tmp_path):
    # On the chip a section that raised is an error dict in the line AND
    # a non-zero exit code — after every remaining section has run.
    order = []
    _stub_tpu_sections(monkeypatch, tmp_path, order,
                       fail=("onebit_pallas",))
    assert bench.inner_main() == 1
    cap = capsys.readouterr()
    assert order[-1] == "bf16_three_d" and "train" in order
    final = json.loads(cap.out.strip().splitlines()[-1])
    assert "refused by the compiler" in final["onebit_pallas"]["error"]
    assert final["value"] == 500.0
    assert "onebit_pallas" in cap.err


def test_inner_main_without_tpu_or_cpu_request_fails_and_prints_nothing(
        monkeypatch, capsys):
    # this suite's devices are CPU devices; without the explicit request
    # the inner must not turn them into a record
    monkeypatch.setattr(bench, "_cpu_requested", lambda: False)
    monkeypatch.delenv("_BPS_BENCH_ONLY", raising=False)
    assert bench.inner_main() != 0
    cap = capsys.readouterr()
    assert cap.out == "" and "no TPU" in cap.err


def test_cpu_requested_reads_only_an_explicit_cpu_first_value(monkeypatch):
    for val, want in (("cpu", True), ("cpu,tpu", True), (" CPU ", True),
                      ("tpu,cpu", False), ("tpu", False), ("", False)):
        monkeypatch.setenv("JAX_PLATFORMS", val)
        assert bench._cpu_requested() is want, val
    monkeypatch.delenv("JAX_PLATFORMS")
    assert bench._cpu_requested() is False


def test_main_exit_code_follows_the_inner(monkeypatch, capsys, tmp_path):
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    monkeypatch.setenv("_BPS_BENCH_REPO", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    for merge in ("_merge_dcn_compare", "_merge_scaling",
                  "_merge_mechanisms", "_merge_overlap",
                  "_merge_async_vs_sync", "_merge_aot_memory",
                  "_couple_overlap_to_projection"):
        monkeypatch.setattr(bench, merge, lambda line: line)
    calls = []

    def inner(result):
        def f(extra_env=None, timeout=bench._INNER_TIMEOUT):
            calls.append(extra_env)
            return result
        return f

    # no chip, no result: non-zero, and NO line on stdout (no retry, no
    # CPU fallback, no value-0 record)
    monkeypatch.setattr(bench, "_run_inner", inner((None, "rc=2")))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert bench.main() == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "rc=2" in cap.err
    assert calls == [None]              # one run, on the default backend
    # a line from an inner that exited non-zero is printed, then fails
    line = json.dumps({"metric": "m", "value": 500.0, "unit": "u",
                       "vs_baseline": 1.0, "device": "TPU v5 lite"})
    monkeypatch.setattr(bench, "_run_inner",
                        inner((line, "inner bench exited 1")))
    assert bench.main() == 1
    assert json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])["value"] == 500.0
    # a clean inner: zero; an explicit CPU request adds the 8-device mesh
    monkeypatch.setattr(bench, "_run_inner", inner((line, None)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.main() == 0
    assert "xla_force_host_platform_device_count=8" in calls[-1]["XLA_FLAGS"]


def test_run_inner_reports_a_nonzero_inner(monkeypatch, capsys):
    class P:
        stdout = "{\"value\": 1.0}\n"
        stderr = "bench: section(s) raised: train"
        returncode = 1

    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: P())
    line, err = bench._run_inner()
    assert json.loads(line) == {"value": 1.0}
    assert err == "inner bench exited 1"


def test_peak_flops_raises_on_an_unknown_device_kind():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        bench._peak_flops("TPU v9 hyper")


def test_assemble_cpu_mesh_line_carries_no_per_chip_name():
    train = {"on_tpu": False, "per_chip": 34.0, "mfu": None,
             "tokens_per_sec_per_chip": 1088.0, "device_kind": "cpu",
             "n_devices": 8, "seq_len": 32, "per_dev_batch": 2}
    result = bench._assemble({"device": {"device_kind": "cpu",
                                         "n_devices": 8, "on_tpu": False},
                              "train": train})
    assert result["device"] == "cpu" and result["value"] == 34.0
    assert not any("per_chip" in k for k in result)
    assert "per_chip" not in result["metric"]


def test_push_pull_ablations_skip_when_projected_slow(monkeypatch):
    # Window economy: a catastrophically slow hardware engine must not
    # spend the green window on secondary ablations — but the headline
    # engine figure itself always runs.  A stepping clock makes every
    # per-rep median enormous (and the headline round to 0.0 GB/s, the
    # slowest case, which must hit the skip rather than dodge it).
    import jax
    ticks = [0.0]

    def fake_clock():
        # two calls per rep (t0 and the delta read) -> 62 s per rep,
        # projecting 8 x 62 = 496 s per ablation, past the 240 s budget
        ticks[0] += 31.0
        return ticks[0]

    monkeypatch.setattr(bench.time, "perf_counter", fake_clock)
    out = bench._bench_push_pull(jax.devices(), on_tpu=False)
    assert "ablations_skipped" in out
    assert "engine_8MB" in out                 # headline still measured
    assert "engine_8MB_no_priority" not in out


# --- round-5 finalize pipeline: compact final line + committed full ---
# record (VERDICT r4 task 1: rounds 3-4 had parsed:null because the
# ~10 kB final line outgrew the driver's 2000-char tail capture).


def _rich_line():
    return json.dumps({
        "metric": "bert_large_mlm_train_throughput_per_chip",
        "value": 526.4, "unit": "examples/s", "vs_baseline": 0.985,
        "mfu": 0.752, "device": "TPU v5 lite", "n_devices": 1,
        "push_pull_gbps": {"fused_256MB": 34.69, "fused_256MB_iqr": [34, 35],
                           "engine_256MB": 0.026, "engine_device_256MB": 11.0,
                           "engine_1MB": 0.013},
        "tpu_overlap": {"overlap_fraction": 0.4},
        "overlap": {"overlap_fraction": -0.061, "conditions": {"c": 1}},
        "flash_attention": {"error": "chip dropped", "fwd_ms": 11.5},
        "bf16_fsdp_tp": {"skipped": "cpu run"},
        "scaling": {"weak": [1, 2, 3]},
        "mechanisms": {"priority": {"m": 1.6}},
    })


def test_finalize_writes_full_record_and_compact_line(tmp_path, monkeypatch,
                                                      capsys):
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    (tmp_path / "BENCH_r03.json").write_text("{}")
    (tmp_path / "BENCH_r04.json").write_text("{}")
    compact = bench._finalize(_rich_line())
    # final line parses, is small, and points at the committed record
    assert len(compact) <= bench._COMPACT_BUDGET
    doc = json.loads(compact)
    assert doc["value"] == 526.4 and doc["mfu"] == 0.752
    assert doc["full_record"] == "BENCH_FULL.json"
    assert doc["round"] == 5                     # one past newest BENCH_r
    # per-section status flags: ok / skip / error+data
    assert doc["sections"]["push_pull_gbps"] == "ok"
    assert doc["sections"]["bf16_fsdp_tp"] == "skip"
    assert doc["sections"]["flash_attention"] == "error+data"
    # headline figures survive compaction: largest-size engine/fused +
    # both overlap fractions
    assert doc["headline"]["fused_256MB_gbps"] == 34.69
    assert doc["headline"]["engine_256MB_gbps"] == 0.026
    assert doc["headline"]["engine_device_256MB_gbps"] == 11.0
    assert doc["headline"]["tpu_overlap_fraction"] == 0.4
    assert doc["headline"]["host_overlap_fraction"] == -0.061
    # the full record is on disk AND echoed as a BENCH_FULL stdout line
    full = json.loads((tmp_path / "BENCH_FULL.json").read_text())
    assert full["push_pull_gbps"]["engine_1MB"] == 0.013
    assert full["scaling"] == {"weak": [1, 2, 3]}
    assert full["recorded"] and full["round"] == 5
    streamed = [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("BENCH_FULL ")]
    assert len(streamed) == 1
    assert json.loads(streamed[0][len("BENCH_FULL "):]) == full


def test_finalize_terminal_failure_line_stays_compact(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    line = json.dumps({"metric": "m", "value": 0.0, "unit": "examples/s",
                       "vs_baseline": 0.0, "error": "x" * 5000})
    compact = bench._finalize(line)
    assert len(compact) <= bench._COMPACT_BUDGET
    assert len(json.loads(compact)["error"]) <= 200


def test_finalize_unparseable_line_passes_through(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    assert bench._finalize("not json") == "not json"
    assert not (tmp_path / "BENCH_FULL.json").exists()


def test_quantile_raw_feeds_rates_without_rounding_collapse():
    # advisor r4: a sub-50 ns median rounds to 0.0 ms at 4 digits; rates
    # must come from the unrounded seconds
    from tools._bench_util import quantile_stats_raw
    med, q25, q75 = quantile_stats_raw([4e-8, 4e-8, 4e-8])
    assert med == 4e-8 and q25 == 4e-8 and q75 == 4e-8
    gbps = 1024 / med / 1e9          # finite, no ZeroDivisionError
    assert gbps > 0


def test_full_record_displacement_guard(tmp_path, monkeypatch):
    # code-review r5: a red round's terminal-failure line must not clobber
    # the numbers-of-record file; it lands in BENCH_FULL_LATEST.json only.
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    tpu = json.dumps({"metric": "m", "value": 526.0, "unit": "u",
                      "vs_baseline": 1.0, "device": "TPU v5 lite"})
    bench._finalize(tpu)
    fail = json.dumps({"metric": "m", "value": 0.0, "unit": "u",
                       "vs_baseline": 0.0, "error": "tpu unavailable"})
    bench._finalize(fail)
    record = json.loads((tmp_path / "BENCH_FULL.json").read_text())
    latest = json.loads((tmp_path / "BENCH_FULL_LATEST.json").read_text())
    assert record["value"] == 526.0          # record survived
    assert latest["value"] == 0.0            # latest shows the red run
    # a complete CPU evidence record does not displace a TPU record...
    cpu = json.dumps({"metric": "m", "value": 34.0, "unit": "u",
                      "vs_baseline": 0.0, "device": "cpu",
                      "mechanisms": {"m": 1}})
    bench._finalize(cpu)
    assert json.loads(
        (tmp_path / "BENCH_FULL.json").read_text())["value"] == 526.0
    # ...but does displace an equal-or-lower class (another CPU record)
    (tmp_path / "BENCH_FULL.json").write_text(cpu)
    cpu2 = json.dumps({"metric": "m", "value": 35.0, "unit": "u",
                       "vs_baseline": 0.0, "device": "cpu"})
    bench._finalize(cpu2)
    assert json.loads(
        (tmp_path / "BENCH_FULL.json").read_text())["value"] == 35.0


def test_run_inner_echoes_section_stream(monkeypatch, capsys):
    # The echo lets a consumer of the OUTER's partial stdout reassemble
    # the sections too.
    sec = "BENCH_SECTION " + json.dumps({"key": "device", "value": {}})

    class P:
        stdout = sec + "\n{\"value\": 1.0}\n"
        stderr = ""
        returncode = 0

    monkeypatch.setattr(bench.subprocess, "run",
                        lambda *a, **k: P())
    line, err = bench._run_inner()
    assert err is None and json.loads(line) == {"value": 1.0}
    assert sec in capsys.readouterr().out


def test_async_bench_tool_emits_convergence_datum(capsys, monkeypatch):
    # round-5: the async-PS convergence datum (VERDICT r4 task 7) — the
    # tool runs both modes and reports the final-loss gap with conditions
    from tools import async_bench as ab
    monkeypatch.setenv("BYTEPS_BENCH_PIN", "off")  # in-process run must
    monkeypatch.setattr(ab, "STEPS", 12)           # not shrink pytest's
    assert ab.main() == 0                          # CPU affinity
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workers"] == 2 and out["steps_per_worker"] == 12
    assert {"loss_init", "loss_sync", "loss_async", "final_loss_gap",
            "async_converged", "conditions"} <= set(out)
    assert out["loss_sync"] < out["loss_init"]       # sync made progress
    assert out["delta_pushes_per_key"] == 2 * 12     # no pushes lost


def test_bf16_three_d_section_single_device():
    # round-5 (VERDICT r4 task 8): the bf16 3D section adapts its mesh to
    # the device count; at one device it degenerates to (1,1,1), which is
    # safe even on the CPU emitter (the CHECK needs real multi-device
    # partial-manual psum) — exactly what a 1-chip green window runs.
    import jax
    out = bench._bench_bf16_three_d(jax.devices()[:1])
    assert out["dtype"] == "bfloat16"
    assert out["mesh"] == "dp=1 x pp=1 x tp=1"
    assert len(out["losses"]) == 8 and out["decreased"]
    assert "trivial at (1,1,1)" in out["note"]


def test_bench_smoke_floor_and_gate_arithmetic(tmp_path, monkeypatch):
    # round-6 (ISSUE 5): the bench-smoke lane gates the engine-vs-fused
    # ratio against the checked-in floor; pin the floor file's shape and
    # the gate arithmetic without running the (minutes-long) measurement
    from tools import bench_smoke as bs
    with open(bs.FLOOR_PATH) as f:
        floor = json.load(f)
    assert 0 < floor["engine_vs_fused_ratio"] <= 4
    assert floor["engine_8MB_gbps"] > 0
    measured = {"fused_8MB_gbps": 1.0, "engine_8MB_gbps": 0.5,
                "engine_vs_fused_ratio": 0.5, "ratio_per_rep": [0.5],
                "autotune": {}}
    monkeypatch.setattr(bs, "_measure", lambda: dict(measured))
    # synthetic passing lanes: the compressed measurement is seconds of
    # real pushes — its gate arithmetic is pinned separately below
    monkeypatch.setattr(bs, "_measure_compressed", lambda: {
        "onebit": {"wire_ratio": 0.031, "gbps": 0.02,
                   "throughput_ratio": 0.1, "golden_error": 0.27,
                   "zero_compile": True},
        "randomk": {"wire_ratio": 0.5, "gbps": 0.001,
                    "throughput_ratio": 0.01, "golden_error": 0.47,
                    "zero_compile": True}})
    # the trace lane is likewise seconds of real pushes; its gate
    # arithmetic is pinned in tests/test_trace_merge.py
    monkeypatch.setattr(bs, "_measure_trace", lambda: {
        "sample_n": 4, "overhead_ratio": 0.95, "events_buffered": 8,
        "events_dropped": 0})
    # the fleet measurement spawns real host processes and churns them
    # for seconds; its gate arithmetic is pinned separately below
    monkeypatch.setattr(bs, "_measure_fleet", lambda: {
        "base_hosts": 2, "peak_hosts": 4, "pulls_per_s": 1e9,
        "p50_ms": 0.1, "p99_ms": 1.0, "pushes_per_s": 10.0,
        "failed_reads": 0, "spawned": 4, "drain_started": 2,
        "drained": 2, "drain_escalated": 0, "banned": 0,
        "final_hosts": 2, "still_draining": []})
    # the durability measurement is seconds of real journaled pushes
    # plus a cold replay; its gate arithmetic is pinned separately below
    monkeypatch.setattr(bs, "_measure_durability", lambda: {
        "push_ratio": 0.6, "ratio_per_rep": [0.6], "replay_records": 401,
        "replay_mb": 25.0, "replay_mbps": 250.0, "truncated_tails": 0,
        "corrupt_records": 0})
    # the other six lanes time real pulls, pushes, sockets and host
    # processes against wall-clock floors: under a loaded host they
    # failed this test for what it does not test; each pure gate is
    # pinned by its own test
    monkeypatch.setattr(bs, "_measure_serve", lambda: {
        "pulls_per_s": 1e4, "p50_ms": 0.1, "p99_ms": 1.0,
        "pushes_per_s": 10.0, "failed_reads": 0, "delta": {"ok": True}})
    monkeypatch.setattr(bs, "_measure_straggler", lambda: {
        "p99_nofault_ms": 0.3, "p99_unhedged_ms": 30.0,
        "p99_hedged_ms": 1.5})
    monkeypatch.setattr(bs, "_measure_sharded_update", lambda: {
        "exact": True, "wire_ratio": 0.5625, "step_time_ratio": 1.0})
    monkeypatch.setattr(bs, "_measure_ts_sampler", lambda: {
        "overhead_ratio": 0.96, "samples": 9})
    monkeypatch.setattr(bs, "_measure_transport", lambda: {
        "tcp_vs_loopback_ratio": 0.75, "partitioned_peer_p99_ms": 2.0})
    monkeypatch.setattr(bs, "_measure_serve_dist", lambda: {
        "failed_reads": 0, "pulls_per_s": 1e9,
        "per_host": {0: {"pulls": 5}, 1: {"pulls": 7}}})
    live = [n for n in vars(bs) if n.startswith("_measure")
            and getattr(getattr(bs, n), "__module__", "") == bs.__name__]
    assert not live, f"lanes that would run for real: {live}"
    monkeypatch.setattr(bs, "setup_cpu8_mesh", lambda: None)
    monkeypatch.setenv("BENCH_SMOKE_TOLERANCE", "0.30")
    monkeypatch.setattr(sys, "argv", ["bench_smoke.py"])
    gate_r = floor["engine_vs_fused_ratio"] * 0.7
    gate_a = floor["engine_8MB_gbps"] * 0.7
    assert bs.main() == (0 if (0.5 >= gate_r or 0.5 >= gate_a) else 1)
    # a fast-regime run: ratio structurally low, absolute honest — passes
    measured.update(engine_vs_fused_ratio=0.35,
                    engine_8MB_gbps=floor["engine_8MB_gbps"] * 2)
    assert bs.main() == 0
    # a round-5-style machinery collapse tanks BOTH floors — fails
    measured.update(engine_vs_fused_ratio=0.2,
                    engine_8MB_gbps=floor["engine_8MB_gbps"] * 0.3)
    assert bs.main() == 1


def test_bench_smoke_serve_dist_floor_and_gate_arithmetic():
    """ISSUE 15: the serve_dist lane gates on zero failed reads
    (absolute), every spawned host actually serving, and aggregate
    pulls/s over the floor with the lane tolerance.  Pin the floor
    file's entry and the pure gate function."""
    from tools import bench_smoke as bs
    with open(bs.FLOOR_PATH) as f:
        floor = json.load(f)
    assert floor["serve_dist_pulls_per_s_floor"] > 0

    def sd():
        return {"failed_reads": 0, "pulls_per_s": 1e9,
                "per_host": {0: {"pulls": 5}, 1: {"pulls": 7},
                             2: {"pulls": 3}}}

    good = sd()
    assert bs._serve_dist_ok(good, floor, 0.3)
    assert good["gate_pulls_per_s"] == round(
        floor["serve_dist_pulls_per_s_floor"] * 0.7, 1)
    # one failed read fails the lane outright — no tolerance
    bad = sd()
    bad["failed_reads"] = 1
    assert not bs._serve_dist_ok(bad, floor, 0.3)
    # a host that never served is a silent death, not a pass
    dead = sd()
    dead["per_host"][2]["pulls"] = 0
    assert not bs._serve_dist_ok(dead, floor, 0.3)
    # a tier-machinery collapse fails the throughput floor
    slow = sd()
    slow["pulls_per_s"] = 0.1
    assert not bs._serve_dist_ok(slow, floor, 0.3)


def test_bench_smoke_fleet_floor_and_gate_arithmetic():
    """ISSUE 18: the fleet lane gates on zero failed reads through
    autoscaler-driven churn (absolute), the churn actually happening
    (spawns to the peak AND at least one graceful drain), drains
    landing clean (none escalated, none stuck), and pulls/s under churn
    over the floor with the lane tolerance.  Pin the floor file's entry
    and the pure gate function."""
    from tools import bench_smoke as bs
    with open(bs.FLOOR_PATH) as f:
        floor = json.load(f)
    assert floor["fleet_pulls_per_s_floor"] > 0

    def fl():
        return {"failed_reads": 0, "pulls_per_s": 1e9, "peak_hosts": 4,
                "spawned": 4, "drained": 2, "drain_escalated": 0,
                "still_draining": []}

    good = fl()
    assert bs._fleet_ok(good, floor, 0.3)
    assert good["gate_pulls_per_s"] == round(
        floor["fleet_pulls_per_s_floor"] * 0.7, 1)
    # one failed read mid-churn fails the lane outright — no tolerance
    bad = fl()
    bad["failed_reads"] = 1
    assert not bs._fleet_ok(bad, floor, 0.3)
    # a bench whose fleet never grew gates nothing — fail loudly
    still = fl()
    still["spawned"] = 2
    assert not bs._fleet_ok(still, floor, 0.3)
    # ...same when no drain ever completed
    nodrain = fl()
    nodrain["drained"] = 0
    assert not bs._fleet_ok(nodrain, floor, 0.3)
    # an escalated (killed) drain is not a graceful scale-down
    esc = fl()
    esc["drain_escalated"] = 1
    assert not bs._fleet_ok(esc, floor, 0.3)
    # a drain still stuck at the end means the deadline machinery broke
    stuck = fl()
    stuck["still_draining"] = [3]
    assert not bs._fleet_ok(stuck, floor, 0.3)
    # a churn-machinery collapse fails the throughput floor
    slow = fl()
    slow["pulls_per_s"] = 0.1
    assert not bs._fleet_ok(slow, floor, 0.3)


def test_bench_smoke_durability_floor_and_gate_arithmetic():
    """ISSUE 19: the durability lane gates on the journal's push-path
    cost ratio and the cold-start replay MB/s (both host measurements,
    lane tolerance), the replay actually reading records back, and a
    clean journal replaying with ZERO damage detected (absolute — torn
    tails or corrupt records on a fault-free bench mean the write path
    itself produces garbage).  Pin the floor file's entries and the
    pure gate function."""
    from tools import bench_smoke as bs
    with open(bs.FLOOR_PATH) as f:
        floor = json.load(f)
    assert 0 < floor["durability_push_ratio_floor"] <= 1
    assert floor["durability_replay_mbps_floor"] > 0

    def du():
        return {"push_ratio": 0.6, "replay_mbps": 250.0,
                "replay_records": 401, "truncated_tails": 0,
                "corrupt_records": 0}

    good = du()
    assert bs._durability_ok(good, floor, 0.3)
    assert good["gate_push_ratio"] == round(
        floor["durability_push_ratio_floor"] * 0.7, 3)
    assert good["gate_replay_mbps"] == round(
        floor["durability_replay_mbps_floor"] * 0.7, 1)
    # the journal taxing the push path fails the ratio floor
    taxed = du()
    taxed["push_ratio"] = 0.01
    assert not bs._durability_ok(taxed, floor, 0.3)
    # a slow cold start fails the replay floor
    slow = du()
    slow["replay_mbps"] = 0.5
    assert not bs._durability_ok(slow, floor, 0.3)
    # a replay that read nothing back gates nothing — fail loudly
    empty = du()
    empty["replay_records"] = 0
    assert not bs._durability_ok(empty, floor, 0.3)
    # damage on a FAULT-FREE run is absolute — no tolerance
    torn = du()
    torn["truncated_tails"] = 1
    assert not bs._durability_ok(torn, floor, 0.3)
    corrupt = du()
    corrupt["corrupt_records"] = 2
    assert not bs._durability_ok(corrupt, floor, 0.3)


def test_bench_smoke_compressed_floor_and_gate_arithmetic():
    """ISSUE 11: the compressed lanes gate on wire ratio (onebit — the
    quantized-reduce-leg contract, <= 0.35x at >= 1 MiB), the
    codec-golden quality ceiling (deterministic, no tolerance), and the
    throughput floor (host measurement, lane tolerance).  Pin the floor
    file's shape and the pure gate function."""
    from tools import bench_smoke as bs
    with open(bs.FLOOR_PATH) as f:
        floor = json.load(f)
    assert 0 < floor["compressed_wire_ratio_max"] <= 0.35
    assert 0 < floor["compressed_quality_ceiling"] <= 1
    assert floor["compressed_throughput_floor"] >= 0

    def lanes():
        return {"onebit": {"wire_ratio": 0.031, "golden_error": 0.27,
                           "throughput_ratio": 0.1},
                "randomk": {"wire_ratio": 0.5, "golden_error": 0.47,
                            "throughput_ratio": 0.01}}

    good = lanes()
    assert bs._compressed_ok(good, floor, 0.3)
    assert good["onebit"]["ok"] and good["randomk"]["ok"]
    # onebit shipping full-precision bytes on the reduce leg — fails
    fat = lanes()
    fat["onebit"]["wire_ratio"] = 0.9
    assert not bs._compressed_ok(fat, floor, 0.3)
    assert not fat["onebit"]["ok"] and fat["randomk"]["ok"]
    # a codec whose golden error broke the quality ceiling — fails
    lossy = lanes()
    lossy["randomk"]["golden_error"] = 0.9
    assert not bs._compressed_ok(lossy, floor, 0.3)
    # a machinery collapse on the compressed path — fails the tput floor
    slow = lanes()
    slow["onebit"]["throughput_ratio"] = 0.0
    assert not bs._compressed_ok(slow, floor, 0.3)
    # randomk's dense wire ratio (0.5 > 0.35) is NOT gated: the wire
    # contract is onebit's — randomk's lane reports it for the trend
    assert lanes()["randomk"]["wire_ratio"] > floor[
        "compressed_wire_ratio_max"]


def test_bench_smoke_sharded_update_floor_and_gate_arithmetic():
    """ISSUE 20: the sharded_update lane gates on the wire-ratio
    contract (push N + pull N/R — deterministic, no tolerance), the
    bitwise replay exactness (absolute), and the interleaved step-time
    ratio over the floor with the lane tolerance.  Pin the floor file's
    entries and the pure gate function."""
    from tools import bench_smoke as bs
    with open(bs.FLOOR_PATH) as f:
        floor = json.load(f)
    assert floor["sharded_wire_ratio_max"] <= 0.62
    assert floor["sharded_step_ratio_floor"] > 0

    def su():
        return {"exact": True, "wire_ratio": 0.577,
                "step_time_ratio": 1e9}

    good = su()
    assert bs._sharded_update_ok(good, floor, 0.3)
    assert good["gate_step_ratio"] == round(
        floor["sharded_step_ratio_floor"] * 0.7, 3)
    # trajectory drift fails outright — the replay proof is absolute
    drift = su()
    drift["exact"] = False
    assert not bs._sharded_update_ok(drift, floor, 0.3)
    # the wire ratio is the feature's contract — no tolerance applied
    fat = su()
    fat["wire_ratio"] = floor["sharded_wire_ratio_max"] + 0.01
    assert not bs._sharded_update_ok(fat, floor, 0.3)
    # an update-machinery collapse fails the step-time floor
    slow = su()
    slow["step_time_ratio"] = 0.0
    assert not bs._sharded_update_ok(slow, floor, 0.3)
