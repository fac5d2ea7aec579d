"""The cell ``evabyte_6b5.fused_1c`` (ISSUE 50): its configuration against
the published ``config.json`` (the guide's catalog row), the parameter
count, the family's operation counts against hand arithmetic, its readers
on a made-up trace, its entries in BENCHMARK.json (found BY NAME and
"after", never "last"), the reference's two copies held to one text, the
gradient comparison and its breaks on the toy, and the rehearsal's contract
line (the new metrics asserted as a SUBSET of what it carries)."""

import contextlib
import json
import os
import re
import subprocess
import sys
import types

import pytest

from harness import spec, xplane
from harness.peaks import peaks_for

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import gradcheck_evabyte as gradcheck  # noqa: E402

CELL = "evabyte_6b5.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
FAMILY = spec.load_module("families", "evabyte")
NEW = ["eva_summary_ms", "eva_summary_roofline", "eva_visited_block_share"]
SEQ = 16384               # the ladder's rung (a): eight windows of 2 048
WINDOWS = SEQ // 2048

# EvaByte/EvaByte config.json (the guide's catalog row)
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_depth():
    assert len(PUBLISHED) == 29
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert entry("configs", "evabyte_6b5")["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 4
    assert CONFIG["num_hidden_layers_published"] == 32          # the twin
    assert {"pooling", "remote_set", "one_softmax", "prediction_heads",
            "norms", "residual", "init", "hidden_act", "rope",
            "training_length", "dtypes", "weights", "data"} <= set(
                CONFIG["assumed"])
    assert "AFTER the rotation" in CONFIG["assumed"]["pooling"]
    assert "EXCLUDES the query's own window" in CONFIG["assumed"]["remote_set"]
    for said in ("8 pipeline stages of 4 whole layers", "one chip a stage",
                 "the last stage's tail"):
        assert said in CONFIG["deployment"]
    for said in ("rung (a)", "rung (b)", "rung (c)", "TAKEN"):
        assert said in CONFIG["notes"]
    assert entry("configs", "evabyte_6b5")["source"] == CONFIG["source"] == (
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json")
    assert TRAFFIC["seq_len"] == SEQ and TRAFFIC["remat"] is True
    assert TRAFFIC["attention"] == "flash"
    assert TRAFFIC["seqs_per_chip"] == TRAFFIC["reference_microbatch"] == 1
    assert TRAFFIC["optimizer"] == {"name": "adamw", "learning_rate": 1e-4}
    assert entry("workloads", CELL)["traffic"] == "clm16384_fused"
    why = entry("workloads", CELL)["why"]
    assert "depth 4 of 32, every layer whole" in why and "EVA" in why


def test_the_cut_is_821_million_parameters():
    layer = 4 * 4096 ** 2 + 2 * 32 * 128 + 3 * 4096 * 11008 + 2 * 4096
    assert layer == 202_391_552
    assert FAMILY.share_params(CONFIG) == (
        4 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096) == 821_366_784
    # 6.5 B as published (the catalog's "6.5B")
    uncut = FAMILY.share_params(dict(CONFIG, num_hidden_layers=32))
    assert uncut == 32 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096
    assert 6.4e9 < uncut < 6.6e9


def test_operation_counts_against_hand_arithmetic():
    family = FAMILY.build(CONFIG, TRAFFIC)
    work = family.kernel_work(1)
    assert set(work) == {"flash", "eva_summary"}
    # a row of window w sees 128 w summaries: 2048 x 128 x (0 + 1 + .. + 7)
    far_pairs = 2048 * 128 * WINDOWS * (WINDOWS - 1) // 2
    near_pairs = WINDOWS * 2048 * 2048 / 2
    assert FAMILY.summary_pairs_per_seq(CONFIG, SEQ) == far_pairs
    summary = work["eva_summary"]
    assert summary["flops"] == 4 * 32 * 14 * 128 * far_pairs
    assert summary["bytes"] == 4 * ((SEQ + SEQ // 16) * 6 * 32 * 128 * 2
                                    + 12 * SEQ * 32)
    flash = work["flash"]
    assert flash["flops"] == 4 * 32 * 14 * 128 * (far_pairs + near_pairs)
    assert flash["bytes"] == summary["bytes"] + 4 * (
        SEQ * 12 * 32 * 128 * 2 + 12 * SEQ * 32)
    weights = 4 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 8 * 320
    assert family.flops_per_token == pytest.approx(
        6 * weights + 4 * 32 * 14 * 128 * (far_pairs + near_pairs) / SEQ)
    # EVA's needed scores: ~6 % of a token's operations at this length
    assert 0.05 < 1 - 6 * weights / family.flops_per_token < 0.08
    fwd = "jit(step)/jvp(EvaByte)/h0/attn/bps.eva.{}/pallas_call"
    bwd = ("jit(step)/transpose(jvp(EvaByte))/jvp(EvaByte)/checkpoint/"
           "{}h3/attn/bps.eva.{}/pallas_call")
    for rule, both in ((summary["op_name_re"], False),
                       (flash["op_name_re"], True)):
        assert re.search(rule, fwd.format("summary"))
        assert re.search(rule, bwd.format("", "summary"))
        assert re.search(rule, bwd.format("rematted_computation/", "summary"))
        assert bool(re.search(rule, fwd.format("local"))) is both
        assert not re.search(rule, "jit(step)/jvp(EvaByte)/h0/attn/"
                             "bps.eva.pool/reduce_sum")
    with pytest.raises(spec.SpecError, match="whole windows"):
        FAMILY.flops_per_token(CONFIG, 3000)


def _made_up_run(steps=2):
    """Two steps; per step and layer the own-window forward 4 ms, its
    recomputation 4, its one-kernel backward 9; the summaries' forward 1
    ms, recomputed 1, the two-kernel backward 2 + 1 (x 4 layers: 68 ms
    local, 20 ms summary); a fusion under the pooling's scope."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd = "jit(step)/jvp(EvaByte)/{}/attn/bps.eva.{}/pallas_call"
    bwd = ("jit(step)/transpose(jvp(EvaByte))/jvp(EvaByte)/checkpoint/{}"
           "{}/attn/bps.eva.{}/pallas_call")
    for _ in range(steps):
        for layer in ("h0", "h1", "h2", "h3"):
            op(f"l.f.{layer}", 4, fwd.format(layer, "local"))
            op(f"s.f.{layer}", 1, fwd.format(layer, "summary"))
            op(f"l.r.{layer}", 4, bwd.format("rematted_computation/", layer,
                                             "local"))
            op(f"s.r.{layer}", 1, bwd.format("rematted_computation/", layer,
                                             "summary"))
            op(f"l.b.{layer}", 9, bwd.format("", layer, "local"))
            op(f"s.b0.{layer}", 2, bwd.format("", layer, "summary"))
            op(f"s.b1.{layer}", 1, bwd.format("", layer, "summary"))
            op(f"fusion.{layer}", 3)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    family = FAMILY.build(CONFIG, TRAFFIC)
    gauges = {"eva.visited_block_share": 0.55, "eva.summary_keys": 1024.0,
              "eva.saved_lse_bytes": 2097152.0}
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work=family.kernel_work(1), peaks=peaks_for("TPU v5 lite"),
        snap1={"gauges": gauges}, family=family,
        job=types.SimpleNamespace(seqs_per_chip=1))


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_readers_on_a_made_up_trace():
    run = _made_up_run()
    assert read("eva_summary_ms", run) == pytest.approx(20.0)
    assert run.info["eva_summary_calls_per_step"] == 16
    assert run.info["eva_calls_per_step"] == 28      # seven a layer
    work = FAMILY.eva_summary_work(CONFIG, SEQ, 1)
    assert read("eva_summary_roofline", run) == pytest.approx(
        100 * max(work["flops"] / 197e12, work["bytes"] / 819e9) / 20e-3,
        rel=1e-6)
    assert run.info["eva_summary_roofline_bound"] in ("compute", "memory")
    assert read("eva_visited_block_share", run) == 0.55
    assert run.info["eva.summary_keys"] == 1024.0
    # the accepted readers the cell is appended to
    assert read("flash_ms", run) == pytest.approx(88.0)
    flash = FAMILY.flash_work(CONFIG, SEQ, 1)
    assert read("flash_roofline", run) == pytest.approx(
        100 * max(flash["flops"] / 197e12, flash["bytes"] / 819e9) / 88e-3,
        rel=1e-6)


@pytest.mark.parametrize("name", ["eva_summary_ms", "eva_summary_roofline"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    """What the parent commit's program gives under these benchmark files:
    nothing, and no exception."""
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # another family
    assert read(name, run) is None
    run = _made_up_run()
    run.mosaic = {}                    # a program without such kernels
    assert read(name, run) is None
    bare = types.SimpleNamespace(snap1={"gauges": {}}, info={})
    assert read("eva_visited_block_share", bare) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    cell = entry("workloads", CELL)
    assert cell == {"name": CELL, "config": "evabyte_6b5",
                    "traffic": cell["traffic"], "chips": 1,
                    "why": cell["why"]}
    for name in NEW:
        m, reader = entry("per_layer", name), spec.load_module(
            "layer_metrics", name)
        assert m["workloads"] == [CELL]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (m["unit"], m["better"], m["source"],
                                  m["layer"], m["moves"])
    reported = {m["name"] for m in spec.metrics_for(BENCH, "per_layer", CELL)}
    assert set(NEW) | {"flash_roofline", "flash_ms", "mfu_pct",
                       "step_device_ms", "device_idle_pct",
                       "compiles_in_window"} <= reported
    cells = entry("per_layer", "flash_roofline")["workloads"]
    assert cells.count(CELL) == 1
    assert cells.index(CELL) > cells.index("qwen3_next_80b.fused_1c")
    for name in ("gdn_scan_ms", "kda_scan_ms", "moe_ms", "moe_roofline",
                 "mla_flash_roofline", "held_moe_ms", "ssm_scan_ms"):
        assert CELL not in entry("per_layer", name)["workloads"]
    # every older cell's metrics are what they were
    for other in (w["name"] for w in BENCH["workloads"]
                  if w["name"] != CELL):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", other)}
    # the new entries stand AFTER everything the parent's benchmark had
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[0]) > names.index("setup_cache_misses")
    assert names[names.index(NEW[0]):names.index(NEW[0]) + len(NEW)] == NEW
    order = [w["name"] for w in BENCH["workloads"]]
    assert order.index(CELL) > order.index("qwen3_next_80b.fused_1c")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("evabyte_6b5") > configs.index("qwen3_next_80b")
    for text in (entry("configs", "evabyte_6b5")["why"],
                 entry("configs", "evabyte_6b5")["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_family_s_reference_is_a_copy_of_the_tests():
    """``families/evabyte.py`` carries ``tests/evabyte_reference.py``
    between the two ``reference`` marks, letter for letter."""
    def between(path):
        text = open(path).read()
        return text[text.index("# " + "-" * 63 + " reference"):
                    text.index("end reference")]
    assert between(os.path.join(spec.BENCH_DIR, "families", "evabyte.py")
                   ) == between(os.path.join(spec.CHECKOUT, "tests",
                                             "evabyte_reference.py"))


# -------------------------------------- the gradient comparison, on the toy

TOY = dict(compute_dtype="float32")


@pytest.fixture(scope="module")
def toy():
    family, seqs = gradcheck.build(True, **TOY)
    params, batch = gradcheck.inputs(family, seqs, 1)
    return (params, batch, gradcheck.reference(family, params, batch),
            gradcheck.attention_reference(family, 1))


def _program_side(toy, fault=None):
    params, batch, want, want_attention = toy
    with gradcheck.broken(fault) if fault else contextlib.nullcontext():
        family, _ = gradcheck.build(True, **TOY)
        return (gradcheck.compare(family, params, batch, want),
                gradcheck.attention_compare(family, 1, want_attention))


def test_gradient_comparison_passes_on_the_toy(toy):
    model, attention = _program_side(toy)
    assert model["ok"], (model["worst_leaf"], model["worst_rel_l2"])
    assert attention["ok"], attention["attention_rel_l2"]
    assert max(model["worst_rel_l2"], model["small_leaves_rel_l2"],
               model["pool_leaves_rel_l2"], model["logits_rel_l2"],
               *attention["attention_rel_l2"].values()) < 1e-3


@pytest.mark.parametrize("what", gradcheck.BREAKS)
def test_gradient_comparison_reads_each_wrong_eva(toy, what):
    """On the toy in float32 the attention ALONE tells every wrong EVA from
    the right one by a hundred times the clean reading (the chip's limits,
    set from the chip's readings, are ``gradcheck_evabyte.py``'s)."""
    _, attention = _program_side(toy, what)
    assert not attention["ok"]
    assert max(attention["attention_rel_l2"].values()) > 0.03
    import byteps_tpu.models.evabyte as model            # undone on exit
    assert model.eva_attention.__module__ == "byteps_tpu.ops.eva_attention"


@pytest.mark.parametrize("what", ["bf16_pool", "bf16_merge"])
def test_gradient_comparison_reads_a_lower_precision(toy, what):
    """The control the chip's limits are read against: the pooling or the
    merge in bfloat16 inside the program reads thousands of times the
    float32 toy's clean reading (4e-7 the attention alone, 2e-7 the
    logits, 6e-7 ``mu`` / ``phi``), through the attention alone (2.1e-2 /
    6.7e-3), the model's logits (1.6e-4 / 6.3e-4) and the pooling
    vectors' gradients (1.7e-2 / 5.3e-3)."""
    model, attention = _program_side(toy, what)
    assert max(attention["attention_rel_l2"].values()) > 5e-3
    assert model["logits_rel_l2"] > 1e-4
    assert model["pool_leaves_rel_l2"] > 4e-3


def _run_cell(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, *args], cwd=spec.CHECKOUT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_rehearsal_prints_the_contract_line_correct():
    p = _run_cell("--seed", "3000000050", "--seconds", "1", "--trace", "1",
                  "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["rehearsal"] is True
    metrics = line["metrics"]
    # counts only on the CPU, never a device metric; asserted as a SUBSET,
    # so that the next PR's appended metric does not fail this test
    assert {"compiles_in_window", "eva_visited_block_share"} <= set(metrics)
    assert not {"eva_summary_ms", "eva_summary_roofline", "flash_ms",
                "flash_roofline", "mfu_pct"} & set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # the toy: 4 windows of 64 in sub-blocks of 64 (one a window, visited)
    # and 4 row blocks x 1 key sub-block of the 32 summaries (3 visited)
    assert metrics["eva_visited_block_share"]["value"] == pytest.approx(
        (4 + 3) / (4 + 4))


def test_without_a_tpu_the_cell_exits_at_once_with_no_line():
    p = _run_cell("--seed", "1", "--seconds", "1", "--trace", "0",
                  timeout=120)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "no TPU" in p.stderr
