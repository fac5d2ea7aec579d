"""The cell ``glm47_flash.fused_1c`` (ISSUE 35): its configuration against
the published ``config.json``, the share's parameter count, the family's
operation counts against hand arithmetic, its five readers on a made-up
trace, its entries in BENCHMARK.json (found BY NAME), the gradient
comparison's limits against eleven deliberate breaks, and the rehearsal's
contract line (the new metrics asserted as a SUBSET of what it carries)."""

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
import gradcheck_glm_lite  # noqa: E402

CELL = "glm47_flash.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
GLM = spec.load_module("families", "glm_lite")
NEW = ["mla_flash_roofline", "routed_moe_ms", "routed_moe_roofline",
       "noaux_held_pair_share", "mtp_kernel_ms"]

# zai-org/GLM-4.7-Flash config.json (the guide's catalog row)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_share():
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs == set(CONFIG["reduced"]) == set(
        entry("configs", "glm47_flash")["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 8, 19360)
    # the published counts stand beside the cut ones
    assert CONFIG["n_routed_experts_published"] == 64
    assert CONFIG["vocab_size_published"] == 154880
    assert CONFIG["num_hidden_layers_published"] == 47
    assert CONFIG["experts_held"] == [0, 8]
    assert CONFIG["mtp_loss_weight"] == 0.3
    assert {"equations", "latent_norms", "rotary_key", "rotary", "noaux_tc",
            "routed_weights", "balancing_bias", "shared_expert",
            "mtp_module", "mtp_loss_weight", "training_length", "dtypes",
            "weights", "data"} <= set(CONFIG["assumed"])
    assert "LEFT OUT" in CONFIG["assumed"]["balancing_bias"]
    assert "rotate-half" in CONFIG["assumed"]["rotary"]
    assert "1e-20" in CONFIG["assumed"]["routed_weights"]
    for said in ("96 chips = 12 pipeline stages", "x 8 chips that share",
                 "706.5 M parameters", "11.30 GB", "10.53 GiB",
                 "WITHOUT its exchange"):
        assert said in CONFIG["deployment"]
    assert entry("configs", "glm47_flash")["source"] == CONFIG["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert (TRAFFIC["seq_len"], TRAFFIC["seqs_per_chip"],
            TRAFFIC["reference_microbatch"]) == (8192, 2, 2)
    assert TRAFFIC["remat"] is True and TRAFFIC["attention"] == "flash"


def test_the_share_is_706_million_parameters():
    # attention: 2048 x 768 = 1,572,864; 768 x 20 x 256 = 3,932,160; 2048 x
    # 576 = 1,179,648; 512 x 20 x 448 = 4,587,520; 5120 x 2048 = 10,485,760
    # -> 21,757,952; the two latent norms 1,280; the two block norms 4,096
    # -> 21,763,328 a block, six blocks; the dense MLP 3 x 2048 x 10240 =
    # 62,914,560; a sparse block's router 131,072 + bias 64 + shared
    # 9,437,184 + 8 x 9,437,184 = 85,065,792, five of them; table + head 2 x
    # 19360 x 2048 = 79,298,560; the last norm 2,048; the module's M
    # 8,388,608 and three norms 6,144
    assert GLM.share_params(CONFIG) == (
        6 * 21_763_328 + 62_914_560 + 5 * 85_065_792 + 79_298_560 + 2_048
        + 8_388_608 + 6_144)
    assert GLM.share_params(CONFIG) == 706_518_848
    assert round(GLM.share_params(CONFIG) * 16 / 1e9, 2) == 11.30
    assert round(GLM.share_params(CONFIG) * 16 / 2 ** 30, 2) == 10.53
    whole = dict(CONFIG, num_hidden_layers=47, n_routed_experts=64,
                 vocab_size=154880, num_nextn_predict_layers=0)
    assert round(GLM.share_params(whole) / 1e9, 2) == 29.94   # "30B-A3B"


def test_the_model_builds_that_many_parameters():
    import jax
    family = GLM.build(CONFIG, TRAFFIC)
    shapes = jax.eval_shape(family.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 706_518_848
    p = shapes["params"]
    assert p["h0"]["mlp"]["gate_proj"]["kernel"].shape == (2048, 10240)
    assert p["h1"]["moe"]["gate"].shape == (8, 2048, 1536)
    assert p["h1"]["moe"]["router"].shape == (2048, 64)
    assert p["h1"]["moe"]["e_score_correction_bias"].shape == (64,)
    attn = p["h4"]["attn_mla"]
    assert attn["q_b_proj"]["kernel"].shape == (768, 20, 256)
    assert attn["kv_a_proj_with_mqa"]["kernel"].shape == (2048, 576)
    assert attn["kv_b_proj"]["kernel"].shape == (512, 20, 448)
    assert attn["o_proj"]["kernel"].shape == (20, 256, 2048)
    assert p["mtp"]["eh_proj"]["kernel"].shape == (4096, 2048)
    assert p["mtp"]["block"]["moe"]["down"].shape == (8, 1536, 2048)
    assert p["wte"]["embedding"].shape == p["lm_head"].shape == (19360, 2048)
    assert "h5" not in p and "wte" not in p["mtp"]
    assert family.experts_held == (0, 8)


def test_flops_per_token_is_3_88_gflop_at_8192():
    # matmul weights a token: six blocks' attention 6 x 21,757,952; the
    # dense MLP 62,914,560; five sparse blocks' router 131,072 + shared
    # 9,437,184 + half a pair x 9,437,184 = 14,286,848 each; M 8,388,608;
    # the head twice 79,298,560 -> 352,583,680 x 6 = 2,115,502,080
    # attention: 6 blocks x 14 x 256 x 20 x 8192 / 2 = 1,761,607,680
    assert GLM.flops_per_token(CONFIG, 8192) == pytest.approx(
        2_115_502_080 + 1_761_607_680)
    assert round(GLM.flops_per_token(CONFIG, 8192) / 1e9, 2) == 3.88


def test_kernel_work_at_the_cell_shape():
    work = GLM.flash_work(CONFIG, 8192, 2)
    assert work["mla_flash"]["flops"] == (6 * 2 * 14 * 20 * 256
                                          * 8192 * 8192 / 2)
    # a block's bytes, 16 384 rows: q, o, dO, dQ (and o, q again) at 20 x
    # 256 = 6 x 5120; k and dK at 20 x 192 + ONE 64 = 2 x 3904; v and dV 2 x
    # 5120, two bytes each; three float32 rows of 20
    block = 16384 * ((6 * 5120 + 2 * 3904 + 2 * 5120) * 2 + 3 * 4 * 20)
    assert work["mla_flash"]["bytes"] == 6 * block
    assert work["flash"] == work["mla_flash"]
    rule = work["mla_flash"]["op_name_re"]
    for op in ("jit(step)/jvp(GlmLite)/h0/attn_mla/pallas_call",
               "jit(step)/transpose(jvp(GlmLite))/jvp(GlmLite)/checkpoint/"
               "mtp/block/attn_mla/pallas_call"):
        assert re.search(rule, op)
    assert not re.search(rule, "jvp(GlmLite)/h0/attn_mla/bps.mla.latent/mul")
    moe = GLM.moe_work(CONFIG, 8192, 2)
    rows = 16384 * 4 // 8                            # 8,192 live pair rows
    assert moe["flops"] == 5 * 9 * 2 * rows * 2048 * 1536
    assert moe["bytes"] == 5 * 9 * 2 * (8 * 2048 * 1536 + rows * 3584)
    assert GLM.moe_work(CONFIG, 8192, 2, pair_share=0.25)["flops"] == (
        2 * moe["flops"])


def _made_up_run(steps=2):
    """Two steps; per step and block a 10 ms forward, its 10 ms
    recomputation and 12 + 8 ms of backward kernels (x 6 blocks = 240 ms),
    of which the module's 40; per sparse block three grouped matmuls of
    1 ms (x 5 = 15 ms, the module's 3), a gate kernel in the module and a
    fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd = "jit(step)/jvp(GlmLite)/{}"
    bwd = "jit(step)/transpose(jvp(GlmLite))/{}"
    blocks = [f"h{i}" for i in range(5)] + ["mtp/block"]
    for _ in range(steps):
        for block in blocks:
            scope = f"{block}/attn_mla/pallas_call"
            op(f"mla.f.{block}", 10, fwd.format(scope))
            op(f"mla.r.{block}", 10, bwd.format("checkpoint/" + scope))
            op(f"mla.k.{block}", 12, bwd.format(scope))
            op(f"mla.q.{block}", 8, bwd.format(scope))
        for block in blocks[1:]:
            for i in range(3):
                op(f"gmm.{block}.{i}", 1, fwd.format(
                    f"{block}/moe/bps.moe.experts/jit(gmm)/pallas_call"))
        op("gate.mtp", 2, fwd.format(
            "mtp/block/moe/bps.moe.gate/jit(_gate_call)/bps_moe_gate/"
            "pallas_call"))
        op("fusion.9", 4)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    family = GLM.build(CONFIG, TRAFFIC)
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={"moe.held_pair_share": 0.1},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work=family.kernel_work(2), peaks=peaks_for("TPU v5 lite"),
        snap1={"gauges": {}}, family=family,
        job=types.SimpleNamespace(seqs_per_chip=2))


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_readers_on_a_made_up_trace():
    run = _made_up_run()
    assert read("flash_ms", run) == pytest.approx(240.0)
    flash_s = 6 * 2 * 14 * 20 * 256 * 8192 * 8192 / 2 / 197e12
    assert read("mla_flash_roofline", run) == pytest.approx(
        100 * flash_s / 240e-3, rel=1e-6)
    assert run.info["mla_flash_roofline_bound"] == "compute"
    assert read("routed_moe_ms", run) == pytest.approx(15.0)   # not the gate
    moe_s = 5 * 9 * 2 * 8192 * 2048 * 1536 / 197e12
    assert read("routed_moe_roofline", run) == pytest.approx(
        100 * moe_s / 15e-3, rel=1e-6)
    assert run.info["routed_moe_roofline_bound"] == "compute"
    # the batch's own share (0.1, not the expected 0.125) rescales it; the
    # matrices' bytes do not shrink with it, the operations do
    assert run.info["routed_moe_roofline_pct_at_real_share"] == pytest.approx(
        100 * moe_s * 0.8 / 15e-3, rel=1e-6)
    assert read("noaux_held_pair_share", run) == 0.1
    # the module's block: four flash kernels, three grouped matmuls, a gate
    assert read("mtp_kernel_ms", run) == pytest.approx(40 + 3 + 2)


@pytest.mark.parametrize("name", ["mla_flash_roofline", "routed_moe_ms",
                                  "routed_moe_roofline", "mtp_kernel_ms"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # another family
    assert read(name, run) is None


def test_the_share_s_reader_reads_nothing_from_a_program_without_it():
    """A program that lacks the share (another family's, or an older
    commit's under these benchmark files) gives nothing, and does not
    raise."""
    run = types.SimpleNamespace(snap1={"gauges": {}}, info={},
                                family=types.SimpleNamespace())
    assert read("noaux_held_pair_share", run) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    assert entry("workloads", CELL) == {
        "name": CELL, "config": "glm47_flash", "traffic": "clm8192_fused",
        "chips": 1, "why": entry("workloads", CELL)["why"]}
    for name in NEW:
        m, reader = entry("per_layer", name), spec.load_module(
            "layer_metrics", name)
        assert m["workloads"] == [CELL]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (m["unit"], m["better"], m["source"],
                                  m["layer"], m["moves"])
    reported = {m["name"] for m in spec.metrics_for(BENCH, "per_layer", CELL)}
    assert set(NEW) | {"flash_ms", "mfu_pct", "step_device_ms"} <= reported
    # the lists only a benchmark PR may change do not hold the cell
    for name in ("moe_ms", "moe_roofline", "moe_load_max_over_mean",
                 "flash_roofline", "held_moe_ms", "swa_flash_ms",
                 "moe_held_pair_share", "top1_moe_ms", "cca_flash_roofline"):
        assert CELL not in entry("per_layer", name)["workloads"]
        assert name not in reported
    # every older cell's metrics are what they were
    for cell in ("gpt2_medium.fused_1c", "olmoe_1b_7b.fused_1c",
                 "mellum2_12b.fused_1c", "zaya1_8b.fused_1c"):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", cell)}
    # new entries stand at the END of their lists
    assert BENCH["configs"][-1]["name"] == "glm47_flash"
    assert BENCH["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == NEW


def test_the_new_entries_keep_the_contract_s_lengths():
    """A ``why`` and a ``source`` have 1 to 200 characters on one line."""
    for text in (entry("configs", "glm47_flash")["why"],
                 entry("configs", "glm47_flash")["source"],
                 entry("workloads", CELL)["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


# -------------------------------------- the gradient comparison's limits

# the toy as a chip that holds ALL its eight routed experts (the rehearsal
# holds two, a quarter of the routed sum): so weighed, each fault of the
# routed sum shows in float32 as it does at the cell's sizes on the chip
TOY = dict(compute_dtype="float32", n_routed_experts=8, experts_held=[0, 8])


@pytest.fixture(scope="module")
def toy():
    """The comparison of ``gradcheck_glm_lite.py`` on the rehearsal sizes
    in float32 (the toy's 256 tokens of width 64 in bfloat16 are noise; the
    chip's run is the bfloat16 one: PERF.md section 6 PR 35), every
    selection bias moved off zero: the inputs and the reference's side,
    made once.  (Seeds 1-8 read 2.3e-5 to 8.0e-5 in the head's own check
    under bfloat16 logits, but seed 5: 3.2e-6, 64 roundings that cancel.)"""
    family, seqs = gradcheck_glm_lite.build(True, **TOY)
    params, batch = gradcheck_glm_lite.inputs(
        family, seqs, 1, gradcheck_glm_lite.BIAS_FOR_BREAKS)
    return params, batch, gradcheck_glm_lite.reference(family, params, batch)


def _program_side(toy, fault=None):
    params, batch, want = toy
    with (gradcheck_glm_lite.broken(fault) if fault
          else contextlib.nullcontext()):
        # built inside: new closures, so no jit cache outlives the break
        family, _ = gradcheck_glm_lite.build(True, **TOY)
        return gradcheck_glm_lite.compare(family, params, batch, want)


def test_gradient_comparison_passes_on_the_toy(toy):
    out = _program_side(toy)
    assert out["ok"], (out["worst_leaf"], out["worst_rel_l2"])
    # six blocks x (5 matrices + 4 norms) + the dense MLP's 3 + three
    # sparse blocks x (router, bias, 3 stacks, 3 shared) + table, head,
    # last norm + the module's M and 3 norms
    assert len(out["leaves"]) == 4 * 9 + 3 + 3 * 8 + 3 + 4
    assert max(out["worst_rel_l2"], out["small_leaves_rel_l2"]) < 1e-4
    assert max(out["logits_rel_l2"], out["mtp_logits_rel_l2"]) < 1e-4
    assert out["head_rel"] < 1e-6


@pytest.mark.parametrize("what", gradcheck_glm_lite.BREAKS)
def test_gradient_comparison_fails_each_deliberate_break(toy, what):
    """The chip's limits are tight enough on the toy too: ten of the
    faults move a head's logits past ``LOGIT_RTOL`` or some gradient leaf
    of more than ``SMALL_LEAF`` numbers past ``GRAD_RTOL`` by a factor of
    1.3 or more, and logits rounded to bfloat16 before the log-sum-exp —
    which neither the loss nor any gradient can see — move the head's own
    check past ``HEAD_RTOL`` (the chip's own readings: PERF.md section 6
    PR 35)."""
    g = gradcheck_glm_lite
    out = _program_side(toy, what)
    assert not out["ok"]
    if what == "logits_rounded_to_bf16":
        assert out["head_rel"] > 2 * g.HEAD_RTOL
        assert out["worst_rel_l2"] < g.GRAD_RTOL
        assert out["logits_rel_l2"] < g.LOGIT_RTOL
    else:
        assert max(out["worst_rel_l2"] / g.GRAD_RTOL,
                   out["logits_rel_l2"] / g.LOGIT_RTOL,
                   out["mtp_logits_rel_l2"] / g.LOGIT_RTOL) > 1.3, out[
                       "worst_leaf"]
    if what in ("mtp_reads_this_token", "mtp_labels_not_shifted"):
        assert out["logits_rel_l2"] < 1e-4           # the main head: whole
    import byteps_tpu.models.glm_lite as model           # undone on exit
    import byteps_tpu.models.gpt as gpt
    assert model.dropless_moe_mlp.__module__ == "byteps_tpu.parallel.expert"
    assert model.RMSNorm.__name__ == "RMSNorm"
    assert model.apply_rope.__module__ == "byteps_tpu.models.llama"
    for name in ("score_scale", "router_scores", "join_experts",
                 "next_tokens", "mtp_labels"):
        assert getattr(model, name).__name__ == name
    assert gpt._block_logits.__name__ == "_block_logits"


def _run_cell(*args, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, *args], cwd=spec.CHECKOUT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_rehearsal_prints_the_contract_line_correct():
    p = _run_cell("--seed", "3000000019", "--seconds", "1", "--trace", "1",
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
    assert {"compiles_in_window", "noaux_held_pair_share"} <= set(metrics)
    assert not {"mla_flash_roofline", "routed_moe_ms", "routed_moe_roofline",
                "mtp_kernel_ms", "flash_ms", "mfu_pct"} & set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 2 of 8 experts held: a quarter of the pairs, give or take the
    # random router's favourites
    assert 0.05 < metrics["noaux_held_pair_share"]["value"] < 0.6


def test_without_a_tpu_the_cell_exits_at_once_with_no_line():
    p = _run_cell("--seed", "1", "--seconds", "1", "--trace", "0",
                  timeout=120)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "no TPU" in p.stderr
