"""The cell ``nemotron3_super.fused_1c`` (ISSUE 39): its configuration
against the published ``config.json`` (the guide's catalog row), the
share's parameter count, the family's operation counts against hand
arithmetic, its readers on a made-up trace, its entries in BENCHMARK.json
(found BY NAME), the reference's two copies held to one text, the gradient
comparison on the toy, and the rehearsal's contract line (the new metrics
asserted as a SUBSET of what it carries)."""

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
import gradcheck_nemotron_h  # noqa: E402

CELL = "nemotron3_super.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
FAMILY = spec.load_module("families", "nemotron_h")
NEW = ["ssm_scan_ms", "ssm_scan_roofline", "latent_moe_ms",
       "latent_moe_roofline", "latent_held_pair_share", "ssm_state_MiB"]
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
           "n_routed_experts": 8, "vocab_size": 16384, "mamba_num_heads": 16,
           "n_groups": 1, "num_attention_heads": 4, "num_key_value_heads": 1}

# nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json (the guide's
# catalog row)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_share():
    assert len(PUBLISHED["hybrid_override_pattern"]) == 88
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == set(REDUCED) == set(CONFIG["reduced"])
    assert list(REDUCED) == entry("configs", "nemotron3_super")["reduced"]
    for key, held in REDUCED.items():
        assert CONFIG[key] == held
        assert CONFIG[key + "_published"] == PUBLISHED[key]    # the twin
    assert PUBLISHED["hybrid_override_pattern"].startswith(
        CONFIG["hybrid_override_pattern"])
    for kind, count in (("M", 40), ("E", 40), ("*", 8)):       # 5 : 5 : 1
        assert PUBLISHED["hybrid_override_pattern"].count(kind) == count
        assert CONFIG["hybrid_override_pattern"].count(kind) == count // 8
    assert CONFIG["experts_held"] == [0, 8]
    assert CONFIG["mtp_loss_weight"] == 0.3
    assert {"router", "routed_weights", "balancing_bias", "latent_moe",
            "gated_norm", "dt", "convolution", "no_rotation", "mtp_module",
            "mtp_loss_weight", "rescale_prenorm_residual", "training_length",
            "dtypes", "weights", "data"} <= set(CONFIG["assumed"])
    assert "LEFT OUT" in CONFIG["assumed"]["balancing_bias"]
    assert "BEFORE normalising" in CONFIG["assumed"]["gated_norm"]
    assert "NOT clamped" in CONFIG["assumed"]["dt"]
    assert "carried and unused" in CONFIG["assumed"]["no_rotation"]
    for said in ("64 chips share each layer", "x 8 stages",
                 "stage 1 + the last stage's tail", "WITHOUT its exchanges",
                 "a 64th of their share"):
        assert said in CONFIG["deployment"]
    for said in ("rung (a)", "rung (b)", "TAKEN", "REFUSED"):
        assert said in CONFIG["notes"] and said in TRAFFIC["notes"]
    assert entry("configs", "nemotron3_super")["source"] == CONFIG[
        "source"] == ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-"
                      "120B-A12B-BF16/blob/main/config.json")
    assert (TRAFFIC["seq_len"], TRAFFIC["seqs_per_chip"],
            TRAFFIC["reference_microbatch"]) == (8192, 1, 1)
    assert TRAFFIC["remat"] is True and TRAFFIC["attention"] == "flash"
    # the new traffic file is clm8192_fused.json but for the batch
    base = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                       "clm8192_fused.json"))
    assert {k for k in base if base[k] != TRAFFIC.get(k)} == {
        "seqs_per_chip", "reference_microbatch", "notes", "rehearsal"}
    why = entry("workloads", CELL)["why"]
    assert "an eighth of their share" in why and "a 64th" in why


def test_the_share_is_838_million_parameters():
    # an M share: in_proj 4096 x (2 x 1024 + 2 x 128 + 16) = 9,502,720;
    # conv 4 x 1280 + 1280 = 6,400; dt_bias, A_log, D 48; norm 1,024;
    # out_proj 1024 x 4096 = 4,194,304; the block's norm 4,096 -> 13,708,592
    m_block = 9_502_720 + 6_400 + 48 + 1_024 + 4_194_304 + 4_096
    # a * share: q, o 2 x 4096 x 512; k, v 2 x 4096 x 128; norm -> 5,246,976
    a_block = 2 * 4096 * 512 + 2 * 4096 * 128 + 4_096
    # an E block: router 4096 x 512 + bias 512; latent down + up 2 x 4096 x
    # 1024; shared 2 x 4096 x 5376; 8 experts x 2 x 1024 x 2688; norm
    e_block = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 8 * 2 * 1024 * 2688 + 4_096)
    assert (m_block, a_block, e_block) == (13_708_592, 5_246_976, 98_570_752)
    # table + head, the last norm; the module: P 8192 x 4096, three norms,
    # a * share and an E block
    want = (5 * m_block + a_block + 5 * e_block + 2 * 16384 * 4096 + 4_096
            + 8192 * 4096 + 3 * 4_096 + a_block + e_block)
    assert FAMILY.share_params(CONFIG) == want == 838_249_968
    assert round(want * 16 / 2 ** 30, 2) == 12.49
    without = dict(CONFIG, num_nextn_predict_layers=0)
    assert round(FAMILY.share_params(without) * 16 / 2 ** 30, 2) == 10.44


def test_the_model_builds_the_share_s_shapes():
    import jax
    family = FAMILY.build(CONFIG, TRAFFIC)
    p = jax.eval_shape(family.init_params, jax.random.PRNGKey(0))["params"]
    ssm = p["h0"]["mixer_ssm"]
    assert ssm["in_proj"]["kernel"].shape == (4096, 2320)
    assert ssm["conv_kernel"].shape == (4, 1280)
    assert ssm["A_log"].shape == ssm["dt_bias"].shape == ssm["D"].shape == (
        16,)
    assert ssm["out_proj"]["kernel"].shape == (1024, 4096)
    moe = p["h1"]["moe"]
    assert moe["router"].shape == (4096, 512)
    assert moe["up"].shape == (8, 1024, 2688) and "gate" not in moe
    assert moe["down"].shape == (8, 2688, 1024)
    assert moe["fc1_latent_proj"]["kernel"].shape == (4096, 1024)
    assert moe["shared_up_proj"]["kernel"].shape == (4096, 5376)
    attn = p["h7"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (4096, 4, 128)
    assert attn["k_proj"]["kernel"].shape == (4096, 1, 128)
    assert p["mtp"]["eh_proj"]["kernel"].shape == (8192, 4096)
    assert set(p["mtp"]) == {"hnorm", "enorm", "eh_proj", "b0", "b1", "norm"}
    assert "attn" in p["mtp"]["b0"] and "moe" in p["mtp"]["b1"]
    assert p["wte"]["embedding"].shape == p["lm_head"].shape == (16384, 4096)
    assert "h11" not in p and family.experts_held == (0, 8)
    assert family.ssm_shape == (1, 8192, 16, 64, 1, 128, 128)


def test_flops_per_token_is_3_58_gflop_at_8192():
    # matmul weights a token meets: M 9,502,720 + 4,194,304 = 13,697,024;
    # * 5,242,880; E router 2,097,152 + latent 8,388,608 + shared 44,040,192
    # + 22 x 8 / 512 pairs x 2 x 1024 x 2688 = 1,892,352 -> 56,418,304
    weights = (5 * 13_697_024 + 2 * 5_242_880 + 6 * 56_418_304
               + 8192 * 4096 + 2 * 16384 * 4096)
    # the scan, forward a token: C B^T 2 x 128 x 128 + 16 heads x (2 x 128
    # x 64 + 4 x 128 x 64) = 819,200; forward + a backward of twice that
    assert FAMILY.ssd_forward_flops_per_token(CONFIG) == 819_200
    scores = 2 * 14 * 128 * 4 * 8192 / 2
    assert FAMILY.flops_per_token(CONFIG, 8192) == pytest.approx(
        6 * weights + scores + 5 * 3 * 819_200)
    assert round(FAMILY.flops_per_token(CONFIG, 8192) / 1e9, 2) == 3.58


def test_kernel_work_at_the_cell_shape():
    ssd = FAMILY.ssd_work(CONFIG, 8192, 1)
    assert ssd["flops"] == 5 * 4 * 8192 * 819_200          # remat: 4 passes
    assert FAMILY.ssd_work(CONFIG, 8192, 1, remat=False)["flops"] == (
        5 * 3 * 8192 * 819_200)
    # a token's row: xs and y 2 x 1024, B and C 2 x 128 in bf16, dt float32
    # x 16; each and its gradient once
    assert ssd["bytes"] == 5 * 2 * 8192 * ((2 * 1024 + 2 * 128) * 2 + 64)
    for op in ("jit(step)/jvp(NemotronH)/h0/mixer_ssm/bps.ssm.scan/"
               "jit(_forward)/bps_ssd_fwd/pallas_call",
               "jit(step)/transpose(jvp(NemotronH))/h4/mixer_ssm/"
               "bps.ssm.scan/jit(_backward)/bps_ssd_bwd/pallas_call"):
        assert re.search(ssd["op_name_re"], op)
    assert not re.search(ssd["op_name_re"],
                         "jvp(NemotronH)/h0/mixer_ssm/bps.ssm.scan/cumsum")
    flash = FAMILY.flash_work(CONFIG, 8192, 1)
    assert flash["flops"] == 2 * 14 * 4 * 128 * 8192 * 8192 / 2
    assert flash["bytes"] == 2 * 8192 * ((6 * 512 + 4 * 128) * 2 + 3 * 4 * 4)
    assert re.search(flash["op_name_re"],
                     "jit(step)/jvp(NemotronH)/mtp/b0/attn/pallas_call")
    moe = FAMILY.moe_work(CONFIG, 8192, 1)
    rows = 8192 * 22 * 8 / 512                       # 2,816 live pair rows
    assert moe["flops"] == 6 * 6 * 2 * rows * 1024 * 2688
    assert moe["bytes"] == 6 * 6 * 2 * (8 * 1024 * 2688 + rows * 3712)
    assert FAMILY.moe_work(CONFIG, 8192, 1, pair_share=1 / 32)["flops"] == (
        2 * moe["flops"])


def _made_up_run(steps=2):
    """Two steps; per step and M block a 2 ms scan forward, its 2 ms
    recomputation and a 5 ms backward (x 5 = 45 ms); per E block two
    grouped matmuls of 1 ms (x 6 = 12 ms), an activation kernel; per *
    block a 6 ms flash forward; a fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd = "jit(step)/jvp(NemotronH)/{}"
    bwd = "jit(step)/transpose(jvp(NemotronH))/{}"
    scan = "{}/mixer_ssm/bps.ssm.scan/jit({})/bps_ssd_{}/pallas_call"
    for _ in range(steps):
        for block in ("h0", "h2", "h4", "h6", "h9"):
            op(f"ssd.f.{block}", 2, fwd.format(
                scan.format(block, "_forward", "fwd")))
            op(f"ssd.r.{block}", 2, bwd.format(
                "checkpoint/" + scan.format(block, "_forward", "fwd")))
            op(f"ssd.b.{block}", 5, bwd.format(
                scan.format(block, "_backward", "bwd")))
        for block in ("h1", "h3", "h5", "h8", "h10", "mtp/b1"):
            for i in range(2):
                op(f"gmm.{block}.{i}", 1, fwd.format(
                    f"{block}/moe/bps.moe.experts/jit(gmm)/pallas_call"))
            op(f"act.{block}", 3, fwd.format(
                f"{block}/moe/bps.moe.act/jit(_gate_call)/bps_moe_act/"
                "pallas_call"))
        for block in ("h7", "mtp/b0"):
            op(f"flash.{block}", 6, fwd.format(f"{block}/attn/pallas_call"))
        op("fusion.9", 4)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    family = FAMILY.build(CONFIG, TRAFFIC)
    gauges = {"ssm.state_bytes": 524288.0,
              "ssm.saved_state_bytes": 64 * 524288.0, "ssm.heads_held": 16.0,
              "ssm.chunk": 128.0, "ssm.chunks_per_seq": 64.0}
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={"moe.held_pair_share": 0.0125},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work=family.kernel_work(1), peaks=peaks_for("TPU v5 lite"),
        snap1={"gauges": gauges}, family=family,
        job=types.SimpleNamespace(seqs_per_chip=1))


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_readers_on_a_made_up_trace():
    run = _made_up_run()
    assert read("ssm_scan_ms", run) == pytest.approx(45.0)
    ssd_s = 5 * 4 * 8192 * 819_200 / 197e12
    assert read("ssm_scan_roofline", run) == pytest.approx(
        100 * ssd_s / 45e-3, rel=1e-6)
    assert run.info["ssm_scan_roofline_bound"] == "compute"
    assert read("latent_moe_ms", run) == pytest.approx(12.0)  # not the act
    work = FAMILY.moe_work(CONFIG, 8192, 1)
    moe_s = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert read("latent_moe_roofline", run) == pytest.approx(
        100 * moe_s / 12e-3, rel=1e-6)
    assert run.info["latent_moe_roofline_bound"] == "memory"
    real = FAMILY.moe_work(CONFIG, 8192, 1, pair_share=0.0125)
    assert run.info["latent_moe_roofline_pct_at_real_share"] == pytest.approx(
        100 * max(real["flops"] / 197e12, real["bytes"] / 819e9) / 12e-3,
        rel=1e-6)
    assert read("latent_held_pair_share", run) == 0.0125
    assert read("ssm_state_MiB", run) == pytest.approx(32.5)
    assert run.info["ssm.chunks_per_seq"] == 64.0
    assert read("flash_ms", run) == pytest.approx(12.0)
    # the accepted readers the cell is appended to: the two ``*`` blocks'
    # flash calls against the causal half at 4 query heads of 128, and
    # every kernel under the module's scope (2 matmuls, 1 act, 1 flash)
    flash = FAMILY.flash_work(CONFIG, 8192, 1)
    assert read("flash_roofline", run) == pytest.approx(
        100 * max(flash["flops"] / 197e12, flash["bytes"] / 819e9) / 12e-3,
        rel=1e-6)
    assert run.info["flash_roofline_bound"] == "compute"
    assert read("mtp_kernel_ms", run) == pytest.approx(2 * 1 + 3 + 6)


@pytest.mark.parametrize("name", ["ssm_scan_ms", "ssm_scan_roofline",
                                  "latent_moe_ms", "latent_moe_roofline"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # another family
    assert read(name, run) is None


def test_the_counters_readers_read_nothing_from_a_program_without_them():
    """A program that lacks the scan or the share (another family's, or the
    parent commit's under these benchmark files) gives nothing, and does
    not raise."""
    run = types.SimpleNamespace(snap1={"gauges": {}}, info={},
                                family=types.SimpleNamespace())
    assert read("ssm_state_MiB", run) is None
    assert read("latent_held_pair_share", run) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    assert entry("workloads", CELL) == {
        "name": CELL, "config": "nemotron3_super",
        "traffic": "clm8192_b1_fused", "chips": 1,
        "why": entry("workloads", CELL)["why"]}
    for name in NEW:
        m, reader = entry("per_layer", name), spec.load_module(
            "layer_metrics", name)
        assert m["workloads"] == [CELL]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (m["unit"], m["better"], m["source"],
                                  m["layer"], m["moves"])
    reported = {m["name"] for m in spec.metrics_for(BENCH, "per_layer", CELL)}
    assert set(NEW) | {"flash_ms", "mfu_pct", "step_device_ms",
                       "device_idle_pct", "compiles_in_window"} <= reported
    # the lists only a benchmark PR may change do not hold the cell
    for name in ("moe_ms", "held_moe_ms", "routed_moe_ms", "top1_moe_ms",
                 "mla_flash_roofline", "noaux_held_pair_share",
                 "head_logit_block_GiB"):
        assert CELL not in entry("per_layer", name)["workloads"]
        assert name not in reported
    # two accepted metrics whose readers find this family's kernels have
    # the cell APPENDED to their lists, nothing else of them changed
    for name, before in (("flash_roofline", ["gpt2_medium.fused_1c"]),
                         ("mtp_kernel_ms", ["glm47_flash.fused_1c"])):
        assert entry("per_layer", name)["workloads"] == before + [CELL]
        assert name in reported
    # every older cell's metrics are what they were
    for cell in (w["name"] for w in BENCH["workloads"] if w["name"] != CELL):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", cell)}
    # the new entries stand AFTER everything the parent's benchmark had
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[0]) > names.index("mtp_kernel_ms")
    assert names[names.index(NEW[0]):names.index(NEW[0]) + len(NEW)] == NEW
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("glm47_flash.fused_1c")


def test_the_new_entries_keep_the_contract_s_lengths():
    """A ``why`` and a ``source`` have 1 to 200 characters on one line."""
    for text in (entry("configs", "nemotron3_super")["why"],
                 entry("configs", "nemotron3_super")["source"],
                 entry("workloads", CELL)["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_family_s_reference_is_a_copy_of_the_tests():
    """``families/nemotron_h.py`` carries ``tests/nemotron_h_reference.py``
    between the two ``reference`` marks, letter for letter."""
    def between(path):
        text = open(path).read()
        return text[text.index("# " + "-" * 63 + " reference"):
                    text.index("end reference")]
    assert between(os.path.join(spec.BENCH_DIR, "families", "nemotron_h.py")
                   ) == between(os.path.join(
                       spec.CHECKOUT, "tests", "nemotron_h_reference.py"))


# -------------------------------------- the gradient comparison, on the toy

TOY = dict(compute_dtype="float32")


@pytest.fixture(scope="module")
def toy():
    g = gradcheck_nemotron_h
    family, seqs = g.build(True, **TOY)
    params, batch = g.inputs(family, seqs, 1)
    return params, batch, g.reference(family, params, batch)


def _program_side(toy, fault=None):
    import contextlib
    g = gradcheck_nemotron_h
    params, batch, want = toy
    with g.broken(fault) if fault else contextlib.nullcontext():
        family, _ = g.build(True, **TOY)
        return g.compare(family, params, batch, want)


def test_gradient_comparison_passes_on_the_toy(toy):
    out = _program_side(toy)
    assert out["ok"], (out["worst_leaf"], out["worst_rel_l2"])
    assert max(out["worst_rel_l2"], out["small_leaves_rel_l2"],
               out["ssm_leaves_rel_l2"]) < 1e-4
    assert max(out["logits_rel_l2"], out["mtp_logits_rel_l2"]) < 1e-4


@pytest.mark.parametrize("what", ["scaling_dropped", "shared_expert_dropped",
                                  "mtp_reads_this_token"])
def test_gradient_comparison_fails_each_structural_break(toy, what):
    out = _program_side(toy, what)
    g = gradcheck_nemotron_h
    assert not out["ok"]
    assert max(out["worst_rel_l2"] / g.GRAD_RTOL,
               out["logits_rel_l2"] / g.LOGIT_RTOL,
               out["mtp_logits_rel_l2"] / g.LOGIT_RTOL) > 1.3
    import byteps_tpu.models.nemotron_h as model         # undone on exit
    assert model.join_experts.__name__ == "join_experts"
    assert model.next_tokens.__name__ == "next_tokens"
    assert model.NemotronHConfig.__name__ == "NemotronHConfig"


@pytest.fixture(scope="module")
def toy_scan():
    g = gradcheck_nemotron_h
    family, _ = g.build(True, **TOY)
    want = [g.scan_reference(family, 1 + i) for i in range(g.SCAN_SEEDS)]
    return family, want, g.scan_compare(family, 1, want)


def test_the_scan_alone_passes_on_the_toy_as_it_is_and_by_its_stand_in(
        toy_scan):
    """The kernels (interpreted) and the einsum form in their place read
    alike against the recurrence position by position: what the stand-in
    reads under ``bf16_state`` is then the state's rounding alone."""
    g = gradcheck_nemotron_h
    family, want, clean = toy_scan
    with g.broken("einsum_stand_in"):
        stand_in = g.scan_compare(family, 1, want)
    for out in (clean, stand_in):
        assert out["ok"] and not out["fails_every_seed"]
        assert len(out["scan_f32_rel_l2"]) == g.SCAN_SEEDS
        assert max(max(r.values()) for r in out["scan_f32_rel_l2"]
                   ) < g.SCAN_F32_RTOL / 30
    import byteps_tpu.ops.ssd_scan as scan               # undone on exit
    assert scan.ssd_scan.__name__ == "ssd_scan"
    assert scan._chunk_starts.__name__ == "_chunk_starts"


@pytest.mark.parametrize("what", ["bf16_state", "bf16_decays"])
def test_a_lower_precision_in_the_scan_moves_the_scan_s_own_measure(
        toy_scan, what):
    """On float32 operands only the scan's own float32 side is left, and at
    the toy's 128 positions the two precision faults move that measure 30
    to 3 000 times the clean reading and past ``SCAN_F32_RTOL`` on every
    seed (the chip's readings at 8192 positions: PERF.md section 6 PR
    39)."""
    g = gradcheck_nemotron_h
    family, want, clean = toy_scan
    with g.broken(what):
        out = g.scan_compare(family, 1, want)
    worst = max(max(r.values()) for r in clean["scan_f32_rel_l2"])
    assert not out["ok"] and out["fails_every_seed"]
    assert min(max(r.values()) for r in out["scan_f32_rel_l2"]) > max(
        30 * worst, g.SCAN_F32_RTOL)


def _run_cell(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, *args], cwd=spec.CHECKOUT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_rehearsal_prints_the_contract_line_correct():
    p = _run_cell("--seed", "3000000039", "--seconds", "1", "--trace", "1",
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
    assert {"compiles_in_window", "latent_held_pair_share",
            "ssm_state_MiB"} <= set(metrics)
    assert not {"ssm_scan_ms", "ssm_scan_roofline", "latent_moe_ms",
                "latent_moe_roofline", "flash_ms", "mfu_pct"} & set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 2 of 16 experts held: an eighth of the pairs, give or take the
    # random router's favourites
    assert 0.02 < metrics["latent_held_pair_share"]["value"] < 0.4
    # 8 heads x 16 x 8 x 4 B carried + 8 chunks of them saved
    assert metrics["ssm_state_MiB"]["value"] == pytest.approx(
        9 * 8 * 16 * 8 * 4 / 2 ** 20)


def test_without_a_tpu_the_cell_exits_at_once_with_no_line():
    p = _run_cell("--seed", "1", "--seconds", "1", "--trace", "0",
                  timeout=120)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "no TPU" in p.stderr
