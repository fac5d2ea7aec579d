"""The cell ``qwen3_next_80b.fused_1c`` (ISSUE 46): its configuration
against the published ``config.json`` (the guide's catalog row), the
share's parameter count, the family's operation counts against hand
arithmetic, its readers on a made-up trace, its entries in BENCHMARK.json
(found BY NAME and "after", never "last"), the reference's two copies held
to one text, the gradient comparison and its breaks on the toy, and the
rehearsal's contract line (the new metrics asserted as a SUBSET of what it
carries)."""

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
import gradcheck_qwen3_next as gradcheck  # noqa: E402

CELL = "qwen3_next_80b.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
FAMILY = spec.load_module("families", "qwen3_next")
NEW = ["gdn_scan_ms", "gdn_scan_roofline", "gdn_state_MiB", "gdn_rows_ms"]
APPENDED = ["flash_roofline", "moe_ms", "moe_roofline",
            "moe_held_pair_share", "moe_visited_row_share",
            "route_select_ms"]
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}

# Qwen/Qwen3-Next-80B-A3B-Instruct config.json (the guide's catalog row)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_share():
    assert len(PUBLISHED) == 29
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == set(REDUCED) == set(CONFIG["reduced"])
    assert list(REDUCED) == entry("configs", "qwen3_next_80b")["reduced"]
    for key, held in REDUCED.items():
        assert CONFIG[key] == held
        assert CONFIG[key + "_published"] == PUBLISHED[key]    # the twin
    assert CONFIG["experts_held"] == [0, 32]
    assert {"language_model_only", "gdn_block", "gdn_gate",
            "fused_projection_order", "zero_centred_norms",
            "gated_attention", "sparse_mlp", "init", "training_length",
            "dtypes", "weights", "data"} <= set(CONFIG["assumed"])
    assert "LEFT OUT" in CONFIG["assumed"]["language_model_only"]
    assert "log U[1, 16]" in CONFIG["assumed"]["gdn_gate"]
    for said in ("16 chips share each layer", "x 12 stages of 4 layers",
                 "stage 1 + the last stage's tail", "WITHOUT its exchange"):
        assert said in CONFIG["deployment"]
    for said in ("rung (a)", "rung (b)", "TAKEN"):
        assert said in CONFIG["notes"]
    assert entry("configs", "qwen3_next_80b")["source"] == CONFIG["source"] \
        == ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
            "main/config.json")
    assert TRAFFIC["seq_len"] == 8192 and TRAFFIC["remat"] is True
    assert TRAFFIC["attention"] == "flash"
    assert TRAFFIC["reference_microbatch"] == 1
    assert entry("workloads", CELL)["traffic"] in ("clm8192_b4_fused",
                                                   "clm8192_fused")
    why = entry("workloads", CELL)["why"]
    assert "mixers weigh over their share" in why and "a 16th" in why


def test_the_share_is_626_million_parameters():
    assert FAMILY.share_params(CONFIG) == 625_667_136
    uncut = dict(CONFIG, num_experts=512, experts_held=[0, 512],
                 vocab_size=151936, num_hidden_layers=48)
    # 80 B as published (the catalog's "80B-A3B")
    assert 79e9 < FAMILY.share_params(uncut) < 82e9


def test_operation_counts_against_hand_arithmetic():
    family = FAMILY.build(CONFIG, TRAFFIC)
    seqs = TRAFFIC["seqs_per_chip"]
    rows = seqs * 8192
    assert FAMILY.gdn_forward_flops_per_token(CONFIG) == 2 * (
        16 * 16_384 + 32 * 77_952)
    work = family.kernel_work(seqs)
    assert set(work) == {"gdn", "moe", "flash"}
    gdn = work["gdn"]
    assert gdn["flops"] == 3 * 4 * rows * 2 * (16 * 16_384 + 32 * 77_952)
    assert gdn["bytes"] == 3 * 2 * rows * ((2 * 16 + 2 * 32) * 128 * 2
                                           + 2 * 4 * 32)
    moe, pairs = work["moe"], rows * 10 * 32 / 512
    assert moe["flops"] == 4 * 3 * 3 * 2 * pairs * 2048 * 512
    assert moe["bytes"] == 4 * 3 * 3 * 2 * (32 * 2048 * 512
                                            + pairs * (2048 + 512))
    flash = work["flash"]
    assert flash["flops"] == pytest.approx(
        seqs * 16 * 14 * 256 * 8192 * 8192 / 2)
    assert flash["bytes"] == rows * 3 * (2 * 16 * 256 + 2 * 2 * 256) * 2 \
        + 12 * rows * 16
    # 6 a weight a token meets + the scans + the causal half of the scores
    weights = (3 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
               + (2048 * 16 * 512 + 2 * 2048 * 512 + 4096 * 2048)
               + 4 * (2048 * 512 + 2048 + 3 * 2048 * 512
                      + 10 * 32 / 512 * 3 * 2048 * 512) + 2048 * 18992)
    assert family.flops_per_token == pytest.approx(
        6 * weights + 16 * 14 * 256 * 8192 / 2
        + 3 * 3 * 2 * (16 * 16_384 + 32 * 77_952))
    assert re.search(gdn["op_name_re"],
                     "jit(step)/jvp(Qwen3Next)/h0/mixer_gdn/bps.gdn.scan/"
                     "jit(_forward)/bps_gdn_fwd/pallas_call")
    assert re.search(gdn["op_name_re"],
                     "jit(step)/transpose(jvp(bps.gdn.scan))/jit(_backward)/"
                     "bps_gdn_bwd/pallas_call")
    assert not re.search(gdn["op_name_re"],
                         "jit(step)/h0/mixer_gdn/bps.gdn.out/"
                         "jit(_post_forward)/bps_kda_post_fwd/pallas_call")
    assert re.search(flash["op_name_re"],
                     "jit(step)/jvp(Qwen3Next)/h3/attn/pallas_call")
    assert re.search(moe["op_name_re"],
                     "jit(step)/jvp(Qwen3Next)/h2/moe/bps.moe.experts/"
                     "jit(gmm)/pallas_call")


def _made_up_run(steps=2):
    """Two steps; per step and DeltaNet layer a 3 ms scan forward, its 3 ms
    recomputation and a 9 ms backward (x 3 = 45 ms) and the output stage's
    row kernel 1 + 1 + 2 ms (x 3 = 12); the attention layer's flash
    forward 4 ms, recomputed 4, backward 6 + 5; per sparse layer a 1 ms
    selection twice and twelve grouped matmuls of 0.5 ms (x 4 = 24 ms); a
    fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd = "jit(step)/jvp(Qwen3Next)/{}"
    bwd = "jit(step)/transpose(jvp(Qwen3Next))/{}"
    scan = "{}/mixer_gdn/bps.gdn.scan/jit({})/bps_gdn_{}/pallas_call"
    post = "{}/mixer_gdn/bps.gdn.out/jit({})/bps_kda_post_{}/pallas_call"
    for _ in range(steps):
        for layer in ("h0", "h1", "h2"):
            op(f"gdn.f.{layer}", 3, fwd.format(
                scan.format(layer, "_forward", "fwd")))
            op(f"gdn.r.{layer}", 3, bwd.format(
                "checkpoint/" + scan.format(layer, "_forward", "fwd")))
            op(f"gdn.b.{layer}", 9, bwd.format(
                scan.format(layer, "_backward", "bwd")))
            op(f"post.f.{layer}", 1, fwd.format(
                post.format(layer, "_post_forward", "fwd")))
            op(f"post.r.{layer}", 1, bwd.format(
                "checkpoint/" + post.format(layer, "_post_forward", "fwd")))
            op(f"post.b.{layer}", 2, bwd.format(
                post.format(layer, "_post_backward", "bwd")))
        for i, ms in enumerate((4, 4, 6, 5)):
            op(f"flash.{i}", ms, fwd.format("h3/attn/pallas_call"))
        for layer in ("h0", "h1", "h2", "h3"):
            for i in range(2):
                op(f"select.{layer}.{i}", 1, fwd.format(
                    f"{layer}/moe/bps.moe.route/jit(_select_call)/"
                    "bps_moe_select/pallas_call"))
            for i in range(12):
                op(f"gmm.{layer}.{i}", 0.5, (fwd if i < 3 else bwd).format(
                    f"{layer}/moe/bps.moe.experts/jit(gmm)/pallas_call"))
        op("fusion.9", 4)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    family = FAMILY.build(CONFIG, TRAFFIC)
    seqs = TRAFFIC["seqs_per_chip"]
    gauges = {"gdn.state_bytes": 2097152.0,
              "gdn.saved_state_bytes": seqs * 64 * 2097152.0,
              "gdn.heads": 32.0, "gdn.key_heads": 16.0, "gdn.chunk": 128.0,
              "gdn.chunks_per_seq": 64.0,
              "gdn.matmul_operand_bytes_per_chunk": 1.0}
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work=family.kernel_work(seqs), peaks=peaks_for("TPU v5 lite"),
        snap1={"gauges": gauges}, family=family,
        job=types.SimpleNamespace(seqs_per_chip=seqs))


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_readers_on_a_made_up_trace():
    run = _made_up_run()
    seqs = TRAFFIC["seqs_per_chip"]
    assert read("gdn_scan_ms", run) == pytest.approx(45.0)
    assert run.info["gdn_scan_calls_per_step"] == 9
    work = FAMILY.gdn_work(CONFIG, 8192, seqs)
    assert read("gdn_scan_roofline", run) == pytest.approx(
        100 * max(work["flops"] / 197e12, work["bytes"] / 819e9) / 45e-3,
        rel=1e-6)
    assert run.info["gdn_scan_roofline_bound"] == "compute"
    assert read("gdn_rows_ms", run) == pytest.approx(12.0)
    assert run.info["gdn_rows_calls_per_step"] == 9
    assert read("gdn_state_MiB", run) == pytest.approx(2 + seqs * 128)
    assert run.info["gdn.key_heads"] == 16.0
    # the accepted readers the cell is appended to
    assert read("flash_ms", run) == pytest.approx(19.0)
    flash = FAMILY.flash_work(CONFIG, 8192, seqs)
    assert read("flash_roofline", run) == pytest.approx(
        100 * max(flash["flops"] / 197e12, flash["bytes"] / 819e9) / 19e-3,
        rel=1e-6)
    assert read("route_select_ms", run) == pytest.approx(8.0)
    assert run.info["route_select_calls_per_step"] == 8
    assert read("moe_ms", run) == pytest.approx(24.0)
    moe = FAMILY.moe_work(CONFIG, 8192, seqs)
    assert read("moe_roofline", run) == pytest.approx(
        100 * max(moe["flops"] / 197e12, moe["bytes"] / 819e9) / 24e-3,
        rel=1e-6)


@pytest.mark.parametrize("name", ["gdn_scan_ms", "gdn_scan_roofline",
                                  "gdn_rows_ms"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    """What the parent commit's program gives under these benchmark files:
    nothing, and no exception."""
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    if name != "gdn_rows_ms":
        run = _made_up_run()
        run.kernel_work = {}                         # another family
        assert read(name, run) is None
    run = _made_up_run()
    run.mosaic = {}                    # a program without such kernels
    assert read(name, run) is None
    bare = types.SimpleNamespace(snap1={"gauges": {}}, info={})
    assert read("gdn_state_MiB", bare) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    cell = entry("workloads", CELL)
    assert cell == {"name": CELL, "config": "qwen3_next_80b",
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
    assert set(NEW) | set(APPENDED) | {
        "flash_ms", "mfu_pct", "step_device_ms", "device_idle_pct",
        "compiles_in_window"} <= reported
    # the layer answers ``held_rows`` at a 16th held (a window would be an
    # eighth of the pair rows): no windows, so no trips to report
    assert CELL not in entry("per_layer", "moe_window_trips")["workloads"]
    for name in ("kda_scan_ms", "kda_rows_ms", "kda_matmul_operand_KiB",
                 "mla_flash_roofline", "held_moe_ms", "ssm_scan_ms"):
        assert CELL not in entry("per_layer", name)["workloads"]
    # APPENDED: after the cell that held the list's end before
    for name in APPENDED:
        cells = entry("per_layer", name)["workloads"]
        assert cells.count(CELL) == 1
        assert cells.index(CELL) > cells.index("ling3_flash.fused_1c")
    # every older cell's metrics are what they were
    for other in (w["name"] for w in BENCH["workloads"]
                  if w["name"] != CELL):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", other)}
    # the new entries stand AFTER everything the parent's benchmark had
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[0]) > names.index("kda_matmul_operand_KiB")
    assert names[names.index(NEW[0]):names.index(NEW[0]) + len(NEW)] == NEW
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("ling3_flash.fused_1c")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("qwen3_next_80b") > configs.index("ling3_flash")
    for text in (entry("configs", "qwen3_next_80b")["why"],
                 entry("configs", "qwen3_next_80b")["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_family_s_reference_is_a_copy_of_the_tests():
    """``families/qwen3_next.py`` carries ``tests/qwen3_next_reference.py``
    between the two ``reference`` marks, letter for letter."""
    def between(path):
        text = open(path).read()
        return text[text.index("# " + "-" * 63 + " reference"):
                    text.index("end reference")]
    assert between(os.path.join(spec.BENCH_DIR, "families", "qwen3_next.py")
                   ) == between(os.path.join(spec.CHECKOUT, "tests",
                                             "qwen3_next_reference.py"))


# -------------------------------------- the gradient comparison, on the toy

# expert width 48: the stacks are leaves of more than SMALL_LEAF numbers
TOY = dict(compute_dtype="float32", moe_intermediate_size=48)


@pytest.fixture(scope="module")
def toy():
    family, seqs = gradcheck.build(True, **TOY)
    params, batch = gradcheck.inputs(family, seqs, 1)
    return params, batch, gradcheck.reference(family, params, batch)


def _program_side(toy, fault=None):
    params, batch, want = toy
    with gradcheck.broken(fault) if fault else contextlib.nullcontext():
        family, _ = gradcheck.build(True, **TOY)
        return gradcheck.compare(family, params, batch, want)


def test_gradient_comparison_passes_on_the_toy(toy):
    out = _program_side(toy)
    assert out["ok"], (out["worst_leaf"], out["worst_rel_l2"])
    assert max(out["worst_rel_l2"], out["small_leaves_rel_l2"],
               out["gate_leaves_rel_l2"], out["logits_rel_l2"]) < 1e-3


@pytest.mark.parametrize("what", gradcheck.MODEL_BREAKS)
def test_gradient_comparison_reads_each_structural_break(toy, what):
    """On the toy in float32 the clean comparison's worst leaf is a
    router's (a choice that flips on float32's rounding: 0.007-0.015);
    under a break some leaf is wrong by a third and more (the weakest, the
    rotation left out, reads 0.63; the chip's limits, set from the chip's
    readings, are ``gradcheck_qwen3_next.py``'s)."""
    clean, out = _program_side(toy), _program_side(toy, what)
    assert max(clean["leaves"].values()) < 0.05
    assert max(out["leaves"].values()) > 0.3
    import byteps_tpu.models.qwen3_next as model          # undone on exit
    assert model.attention_gate.__name__ == "attention_gate"
    assert model.QkNorm is model.ZeroCentredNorm


def _run_cell(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, *args], cwd=spec.CHECKOUT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_rehearsal_prints_the_contract_line_correct():
    p = _run_cell("--seed", "3000000046", "--seconds", "1", "--trace", "1",
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
    assert {"compiles_in_window", "gdn_state_MiB",
            "moe_held_pair_share"} <= set(metrics)
    assert not {"gdn_scan_ms", "gdn_scan_roofline", "gdn_rows_ms",
                "flash_ms", "mfu_pct", "route_select_ms"} & set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 4 value heads x 16 x 16 x 4 B carried + ONE chunk's of them saved a
    # sequence (128 positions are one chunk of 128)
    seqs = spec.with_rehearsal(TRAFFIC)["seqs_per_chip"]
    assert metrics["gdn_state_MiB"]["value"] == pytest.approx(
        (1 + seqs) * 4 * 16 * 16 * 4 / 2 ** 20)


def test_without_a_tpu_the_cell_exits_at_once_with_no_line():
    p = _run_cell("--seed", "1", "--seconds", "1", "--trace", "0",
                  timeout=120)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "no TPU" in p.stderr
