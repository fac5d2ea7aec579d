"""The cell ``ling3_flash.fused_1c`` (ISSUE 43): its configuration against
the published ``config.json`` (the guide's catalog row), the share's
parameter count, the family's operation counts against hand arithmetic, its
readers on a made-up trace, its entries in BENCHMARK.json (found BY NAME
and "after", never "last"), the reference's two copies held to one text,
the gradient comparison on the toy, and the rehearsal's contract line (the
new metrics asserted as a SUBSET of what it carries)."""

import json
import os
import subprocess
import sys
import types

import pytest

from harness import spec, xplane
from harness.peaks import peaks_for

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import gradcheck_ling  # noqa: E402

CELL = "ling3_flash.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
FAMILY = spec.load_module("families", "ling")
NEW = ["kda_scan_ms", "kda_scan_roofline", "kda_state_MiB",
       "moe_group_hit_share"]
APPENDED = ["flash_roofline", "mla_flash_roofline", "route_select_ms",
            "moe_window_trips", "moe_ms", "moe_roofline",
            "moe_held_pair_share", "moe_visited_row_share"]
REDUCED = {"num_hidden_layers": 6, "num_experts": 8, "vocab_size": 19648}

# inclusionAI/Ling-3.0-flash-VL config.json, the language model's keys (the
# guide's catalog row)
_NO, _E, _S = [0] * 35 + [4] * 7, None, [0] * 34 + [5] * 6 + [7] * 2
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": _NO, "share_expert_swiglu_limit_list": _S}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_share():
    assert len(PUBLISHED) == 51
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == set(REDUCED) == set(CONFIG["reduced"])
    assert list(REDUCED) == entry("configs", "ling3_flash")["reduced"]
    for key, held in REDUCED.items():
        assert CONFIG[key] == held
        assert CONFIG[key + "_published"] == PUBLISHED[key]    # the twin
    assert CONFIG["num_attention_heads"] == 32                 # no head cut
    assert CONFIG["experts_held"] == [0, 8]
    assert {"language_model_only", "kda_block", "kda_gate",
            "full_rank_gates", "no_rotation_in_kda", "mla_gate",
            "group_limited_routing", "balancing_bias", "mtp_module",
            "swiglu_limits", "init", "training_length", "dtypes", "weights",
            "data"} <= set(CONFIG["assumed"])
    assert "LEFT OUT" in CONFIG["assumed"]["balancing_bias"]
    assert "LEFT OUT" in CONFIG["assumed"]["mtp_module"]
    assert "LEFT OUT" in CONFIG["assumed"]["language_model_only"]
    assert "REFUSES" in CONFIG["assumed"]["swiglu_limits"]
    for said in ("64 chips share each layer", "x 7 stages of 6 layers",
                 "stage 1 + the last stage's tail", "WITHOUT its exchange",
                 "vision tower and MTP left out"):
        assert said in CONFIG["deployment"]
    for said in ("rung (a)", "rung (b)", "TAKEN"):
        assert said in CONFIG["notes"]
    assert entry("configs", "ling3_flash")["source"] == CONFIG["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
        "config.json")
    assert (TRAFFIC["seq_len"], TRAFFIC["reference_microbatch"]) == (
        8192, TRAFFIC["seqs_per_chip"])
    assert TRAFFIC["remat"] is True and TRAFFIC["attention"] == "flash"
    assert entry("workloads", CELL)["traffic"] in ("clm8192_fused",
                                                   "clm8192_b1_fused")
    why = entry("workloads", CELL)["why"]
    assert "KDA mixers ~57%" in why and "a 64th of their share" in why


def test_the_share_is_760_million_parameters():
    inner = 32 * 128
    # a KDA mixer: the fused projection 2560 x (5 x 4096 + 32); the taps
    # 4 x 12288; A_log 32; dt_bias 4096; the head norm 128; W_o
    kda = (2560 * (5 * inner + 32) + 4 * 3 * inner + 32 + inner + 128
           + inner * 2560)
    assert kda == 63_049_888
    # an MLA mixer: q 2560 x 32 x 192; the latent 2560 x 576 and its norm;
    # the up-projection 512 x 32 x 256; the gate 2560 x 32; W_o
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256 + 2560 * 32
           + inner * 2560)
    assert mla == 31_965_696
    dense = 3 * 2560 * 6144
    sparse = 2560 * 512 + 512 + 8 * 3 * 2560 * 768 + 3 * 2560 * 768
    norms = 2 * 2560
    want = (2 * (kda + dense + norms) + 3 * (kda + sparse + norms)
            + (mla + sparse + norms) + 2 * 19648 * 2560 + 2560)
    assert want == 759_799_584
    assert FAMILY.share_params(CONFIG) == want
    # 16 B a parameter: 11.32 GiB of state
    assert 11.3 < want * 16 / 2 ** 30 < 11.34


def test_flops_per_token_is_3_5_gflop_at_8192():
    inner = 32 * 128
    kda = 2560 * (5 * inner + 32) + inner * 2560
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + inner * 2560)
    dense = 3 * 2560 * 6144
    sparse = 2560 * 512 + 3 * 2560 * 768 + (8 * 8 / 512) * 3 * 2560 * 768
    weights = 5 * kda + mla + 2 * dense + 4 * sparse + 2560 * 19648
    scan = 32 * 2 * 94_208                   # forward, a token and layer
    assert FAMILY.kda_forward_flops_per_token(CONFIG) == scan
    scores = 32 * 2 * (4 * 192 + 3 * 128) * 8192 / 2
    want = 6 * weights + 5 * 3 * scan + scores
    assert FAMILY.flops_per_token(CONFIG, 8192) == pytest.approx(want)
    assert 3.4e9 < want < 3.7e9
    # the five KDA mixers, projections and scans: ~57 % of a token's work
    share = (6 * 5 * kda + 5 * 3 * scan) / want
    assert 0.53 < share < 0.60


def test_kernel_work_at_the_cell_shape():
    family = FAMILY.build(CONFIG, TRAFFIC)
    seqs = TRAFFIC["seqs_per_chip"]
    work = family.kernel_work(seqs)
    assert set(work) == {"kda", "moe", "flash", "mla_flash"}
    rows = seqs * 8192
    # the held experts: 8 / 512 of the rows' 8 pairs, three matmuls in three
    # passes in each of four sparse layers; the 8 held matrices once a pass
    moe, pairs = work["moe"], rows * 8 * 8 / 512
    assert moe["flops"] == 4 * 3 * 3 * 2 * pairs * 2560 * 768
    assert moe["bytes"] == 4 * 3 * 3 * 2 * (8 * 2560 * 768
                                            + pairs * (2560 + 768))
    kda = work["kda"]
    assert kda["flops"] == 5 * 4 * rows * 32 * 2 * 94_208
    assert kda["bytes"] == 5 * 2 * rows * (4 * 4096 * 2 + 4 * 4096 + 4 * 32)
    assert work["flash"] == work["mla_flash"]
    flash = work["flash"]
    assert flash["flops"] == pytest.approx(
        seqs * 32 * 2 * (4 * 192 + 3 * 128) * 8192 * 8192 / 2)
    assert flash["bytes"] == rows * 3 * (
        32 * 192 + 32 * 128 + 64 + 2 * 32 * 128) * 2 + 12 * rows * 32
    import re
    assert re.search(kda["op_name_re"],
                     "jit(step)/jvp(Ling)/h0/mixer_kda/bps.kda.scan/"
                     "jit(_forward)/bps_kda_fwd/pallas_call")
    assert re.search(kda["op_name_re"],
                     "jit(step)/transpose(jvp(bps.kda.scan))/jit(_backward)/"
                     "bps_kda_bwd/pallas_call")
    assert not re.search(kda["op_name_re"],
                         "jit(step)/h5/attn_mla/pallas_call")
    for op_name in (
            "jit(step)/jvp(Ling)/h2/moe/while/body/bps.moe.window/"
            "bps.moe.experts/jit(gmm)/pallas_call",
            "jit(step)/transpose(jvp(Ling))/jvp(Ling)/checkpoint/h5/moe/"
            "while/body/transpose(jvp(bps.moe.window))/bps.moe.experts/"
            "jit(tgmm)/pallas_call"):
        assert re.search(moe["op_name_re"], op_name)
    assert not re.search(moe["op_name_re"],
                         "jit(step)/jvp(Ling)/h2/moe/bps.moe.route/"
                         "jit(_select_call)/bps_moe_select/pallas_call")


def _made_up_run(steps=2):
    """Two steps; per step and KDA layer a 3 ms scan forward, its 3 ms
    recomputation and a 9 ms backward (x 5 = 75 ms); the MLA layer's flash
    forward 4 ms, recomputed 4 ms, backward 6 + 5 ms; per sparse layer a
    1 ms selection twice and twelve grouped matmuls of 0.5 ms in the
    windows' loops (x 4 = 24 ms); a fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd = "jit(step)/jvp(Ling)/{}"
    bwd = "jit(step)/transpose(jvp(Ling))/{}"
    scan = "{}/mixer_kda/bps.kda.scan/jit({})/bps_kda_{}/pallas_call"
    for _ in range(steps):
        for layer in ("h0", "h1", "h2", "h3", "h4"):
            op(f"kda.f.{layer}", 3, fwd.format(
                scan.format(layer, "_forward", "fwd")))
            op(f"kda.r.{layer}", 3, bwd.format(
                "checkpoint/" + scan.format(layer, "_forward", "fwd")))
            op(f"kda.b.{layer}", 9, bwd.format(
                scan.format(layer, "_backward", "bwd")))
        for i, ms in enumerate((4, 4, 6, 5)):
            op(f"flash.{i}", ms, fwd.format("h5/attn_mla/pallas_call"))
        for layer in ("h2", "h3", "h4", "h5"):
            for i in range(2):
                op(f"select.{layer}.{i}", 1, fwd.format(
                    f"{layer}/moe/bps.moe.route/jit(_select_call)/"
                    "bps_moe_select/pallas_call"))
            for i in range(12):
                op(f"gmm.{layer}.{i}", 0.5, (fwd if i < 3 else bwd).format(
                    f"{layer}/moe/while/body/bps.moe.window/"
                    "bps.moe.experts/jit(gmm)/pallas_call"))
        op("fusion.9", 4)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    family = FAMILY.build(CONFIG, TRAFFIC)
    seqs = TRAFFIC["seqs_per_chip"]
    gauges = {"kda.state_bytes": 2097152.0,
              "kda.saved_state_bytes": seqs * 64 * 2097152.0,
              "kda.heads": 32.0, "kda.chunk": 128.0,
              "kda.chunks_per_seq": 64.0, "kda.log_decay_floor": -5.0}
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
    assert read("kda_scan_ms", run) == pytest.approx(75.0)
    kda_s = 5 * 4 * seqs * 8192 * 32 * 2 * 94_208 / 197e12
    assert read("kda_scan_roofline", run) == pytest.approx(
        100 * kda_s / 75e-3, rel=1e-6)
    assert run.info["kda_scan_roofline_bound"] == "compute"
    assert read("kda_state_MiB", run) == pytest.approx(2 + seqs * 128)
    assert run.info["kda.chunks_per_seq"] == 64.0
    assert run.info["kda.log_decay_floor"] == -5.0
    # the accepted readers the cell is appended to
    assert read("flash_ms", run) == pytest.approx(19.0)
    flash = FAMILY.flash_work(CONFIG, 8192, seqs)
    share = 100 * max(flash["flops"] / 197e12,
                      flash["bytes"] / 819e9) / 19e-3
    assert read("flash_roofline", run) == pytest.approx(share, rel=1e-6)
    assert read("mla_flash_roofline", run) == pytest.approx(share, rel=1e-6)
    assert read("route_select_ms", run) == pytest.approx(8.0)
    assert run.info["route_select_calls_per_step"] == 8
    assert read("moe_ms", run) == pytest.approx(24.0)
    moe = FAMILY.moe_work(CONFIG, 8192, seqs)
    assert read("moe_roofline", run) == pytest.approx(
        100 * max(moe["flops"] / 197e12, moe["bytes"] / 819e9) / 24e-3,
        rel=1e-6)
    assert run.info["moe_roofline_bound"] == "memory"


@pytest.mark.parametrize("name", ["kda_scan_ms", "kda_scan_roofline"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # another family
    assert read(name, run) is None
    run = _made_up_run()
    run.mosaic = {}                    # a program without such kernels
    assert read(name, run) is None


def test_the_counters_readers_read_nothing_from_a_program_without_them():
    """A program that lacks the scan or the group limit (another family's,
    or the parent commit's under these benchmark files) gives nothing, and
    does not raise."""
    run = types.SimpleNamespace(snap1={"gauges": {}}, info={},
                                family=types.SimpleNamespace(),
                                job=types.SimpleNamespace())
    assert read("kda_state_MiB", run) is None
    assert read("moe_group_hit_share", run) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    cell = entry("workloads", CELL)
    assert cell == {"name": CELL, "config": "ling3_flash",
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
    # no sixth copy of the held_moe pair: the first pair's own lists took
    # the cell (``moe_ms`` finds the kernels by the family's key); the
    # copies' lists do not hold it
    for name in ("held_moe_ms", "routed_moe_ms", "top1_moe_ms",
                 "latent_moe_ms", "noaux_held_pair_share",
                 "latent_held_pair_share", "head_logit_block_GiB",
                 "mtp_kernel_ms", "ssm_scan_ms"):
        assert CELL not in entry("per_layer", name)["workloads"]
        assert name not in reported
    # the accepted metrics whose readers find this family's work have the
    # cell APPENDED to their lists, after every cell they held
    for name in APPENDED:
        cells = entry("per_layer", name)["workloads"]
        assert cells[-1] == CELL and cells.count(CELL) == 1
        assert len(cells) >= 2
    # every older cell's metrics are what they were
    for cell in (w["name"] for w in BENCH["workloads"] if w["name"] != CELL):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", cell)}
    # the new entries stand AFTER everything the parent's benchmark had
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[0]) > names.index("route_select_ms")
    assert names[names.index(NEW[0]):names.index(NEW[0]) + len(NEW)] == NEW
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("nemotron3_super.fused_1c")
    configs = [c["name"] for c in BENCH["configs"]]
    assert configs.index("ling3_flash") > configs.index("nemotron3_super")


def test_the_new_entries_keep_the_contract_s_lengths():
    """A ``why`` and a ``source`` have 1 to 200 characters on one line."""
    for text in (entry("configs", "ling3_flash")["why"],
                 entry("configs", "ling3_flash")["source"],
                 entry("workloads", CELL)["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_family_s_reference_is_a_copy_of_the_tests():
    """``families/ling.py`` carries ``tests/ling_reference.py`` between the
    two ``reference`` marks, letter for letter."""
    def between(path):
        text = open(path).read()
        return text[text.index("# " + "-" * 63 + " reference"):
                    text.index("end reference")]
    assert between(os.path.join(spec.BENCH_DIR, "families", "ling.py")
                   ) == between(os.path.join(spec.CHECKOUT, "tests",
                                             "ling_reference.py"))


# -------------------------------------- the gradient comparison, on the toy

# expert width 48: the stacks are leaves of more than SMALL_LEAF numbers
TOY = dict(compute_dtype="float32", moe_intermediate_size=48)


@pytest.fixture(scope="module")
def toy():
    g = gradcheck_ling
    family, seqs = g.build(True, **TOY)
    params, batch = g.inputs(family, seqs, 1)
    return params, batch, g.reference(family, params, batch)


def _program_side(toy, fault=None):
    import contextlib
    g = gradcheck_ling
    params, batch, want = toy
    with g.broken(fault) if fault else contextlib.nullcontext():
        family, _ = g.build(True, **TOY)
        return g.compare(family, params, batch, want)


def test_gradient_comparison_passes_on_the_toy(toy):
    out = _program_side(toy)
    assert out["ok"], (out["worst_leaf"], out["worst_rel_l2"])
    assert max(out["worst_rel_l2"], out["small_leaves_rel_l2"],
               out["kda_leaves_rel_l2"], out["logits_rel_l2"]) < 1e-3


@pytest.mark.parametrize("what", ["group_limit_dropped", "mla_gate_dropped",
                                  "scaling_dropped"])
def test_gradient_comparison_fails_each_structural_break(toy, what):
    out = _program_side(toy, what)
    g = gradcheck_ling
    # at the toy's sizes the leaves a break moves may be "small" ones
    # (<= SMALL_LEAF numbers), which the comparison pools: read them singly
    assert max(max(out["leaves"].values()) / g.GRAD_RTOL,
               out["logits_rel_l2"] / g.LOGIT_RTOL) > 1.1
    import byteps_tpu.models.ling as model                # undone on exit
    assert model.group_limited.__name__ == "group_limited"
    assert model.mla_gate.__name__ == "mla_gate"
    assert model.LingConfig.__name__ == "LingConfig"


@pytest.fixture(scope="module", autouse=True)
def several_chunks_in_the_toy():
    """The toy's 128 positions are ONE chunk of the model's 128; here the
    model asks for chunks of 32, so that a state is handed on."""
    import byteps_tpu.models.ling as model
    patch = pytest.MonkeyPatch()
    patch.setattr(model, "KDA_CHUNK", 32)
    yield
    patch.undo()


@pytest.fixture(scope="module")
def toy_scan():
    g = gradcheck_ling
    family, _ = g.build(True, **TOY)
    want = [g.scan_reference(family, 1 + i) for i in range(g.SCAN_SEEDS)]
    return family, want, g.scan_compare(family, 1, want)


def test_the_scan_alone_passes_on_the_toy_as_it_is_and_by_its_stand_in(
        toy_scan):
    g = gradcheck_ling
    family, want, clean = toy_scan
    with g.broken("chunked_stand_in"):
        stand_in = g.scan_compare(family, 1, want)
    for out in (clean, stand_in):
        assert out["ok"] and not out["fails_every_seed"]
        assert len(out["scan_f32_rel_l2"]) == g.SCAN_SEEDS
        assert max(max(r.values()) for r in out["scan_f32_rel_l2"]
                   ) < g.SCAN_F32_RTOL / 30
    import importlib                                      # undone on exit
    scan = importlib.import_module("byteps_tpu.ops.kda_scan")
    assert scan.kda_scan.__name__ == "kda_scan"
    assert scan._chunk_forward.__name__ == "_chunk_forward"


@pytest.mark.parametrize("what", ["bf16_state", "bf16_decays"])
def test_a_lower_precision_in_the_scan_moves_the_scan_s_own_measure(
        toy_scan, what):
    """On float32 operands only the scan's own float32 side is left, and at
    the toy's 128 positions (four chunks of 32 here) the two
    precision faults move that measure a thousandfold (clean 5e-7, a
    rounded state 1e-3, rounded decays 1.7e-3; the chip's readings at 8192
    positions, which ``SCAN_F32_RTOL`` is set from: PERF.md section 6 PR
    43)."""
    g = gradcheck_ling
    family, want, clean = toy_scan
    with g.broken(what):
        out = g.scan_compare(family, 1, want)
    worst = max(max(r.values()) for r in clean["scan_f32_rel_l2"])
    assert min(max(r.values()) for r in out["scan_f32_rel_l2"]
               ) > 300 * worst


def _run_cell(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, *args], cwd=spec.CHECKOUT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_rehearsal_prints_the_contract_line_correct():
    p = _run_cell("--seed", "3000000043", "--seconds", "1", "--trace", "1",
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
    assert {"compiles_in_window", "kda_state_MiB",
            "moe_group_hit_share"} <= set(metrics)
    assert not {"kda_scan_ms", "kda_scan_roofline", "flash_ms", "mfu_pct",
                "route_select_ms"} & set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 2 of 4 groups kept: half the tokens, give or take the random router
    assert 0.1 < metrics["moe_group_hit_share"]["value"] < 0.9
    # 2 heads x 16 x 16 x 4 B carried + ONE chunk's of them saved a
    # sequence (128 positions are one chunk of 128)
    seqs = spec.with_rehearsal(TRAFFIC)["seqs_per_chip"]
    assert metrics["kda_state_MiB"]["value"] == pytest.approx(
        (1 + seqs) * 2 * 16 * 16 * 4 / 2 ** 20)


def test_without_a_tpu_the_cell_exits_at_once_with_no_line():
    p = _run_cell("--seed", "1", "--seconds", "1", "--trace", "0",
                  timeout=120)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "no TPU" in p.stderr
