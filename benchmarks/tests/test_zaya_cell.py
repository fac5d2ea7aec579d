"""The cell ``zaya1_8b.fused_1c`` (ISSUE 31): its configuration against the
published ``config.json``, the share's parameter count, the family's
operation counts against hand arithmetic, its five readers on a made-up
trace, its entries in BENCHMARK.json (found BY NAME), the gradient
comparison's limits against six deliberate breaks, and the rehearsal's
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
import gradcheck_zaya  # noqa: E402

CELL = "zaya1_8b.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
ZAYA = spec.load_module("families", "zaya")
NEW = ["cca_flash_roofline", "top1_moe_ms", "top1_moe_roofline",
       "top1_held_pair_share", "head_logit_block_GiB"]

# Zyphra/ZAYA1-8B config.json (the guide's catalog row)
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_share():
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs == set(CONFIG["reduced"]) == set(
        entry("configs", "zaya1_8b")["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 8, 131136)
    # the published counts stand beside the cut ones
    assert CONFIG["num_routed_experts"] == 16
    assert CONFIG["vocab_size_published"] == 262272
    assert CONFIG["num_hidden_layers_published"] == 40
    assert CONFIG["experts_held"] == [0, 8]
    assert {"equations", "qk_mean", "convolutions", "value_shift", "qk_norm",
            "rotary", "router_state", "router_mlp", "balancing_bias",
            "expert_weight", "residual_scaling", "mixture_of_depths",
            "parameter_count", "training_length", "dtypes", "weights",
            "data"} <= set(CONFIG["assumed"])
    assert "8.84 B" in CONFIG["assumed"]["parameter_count"]
    assert "LEFT OUT" in CONFIG["assumed"]["balancing_bias"]
    for said in ("20 chips = 10 pipeline stages of 4 layers x 2 chips",
                 "696.2 M parameters", "11.14 GB", "10.37 GiB",
                 "WITHOUT its exchange"):
        assert said in CONFIG["deployment"]
    assert entry("configs", "zaya1_8b")["source"] == CONFIG["source"]
    assert (TRAFFIC["seq_len"], TRAFFIC["seqs_per_chip"],
            TRAFFIC["reference_microbatch"]) == (16384, 1, 1)
    assert TRAFFIC["remat"] is True and TRAFFIC["attention"] == "flash"


def test_the_share_is_696_million_parameters():
    # attention: q, o 2 x 2048 x 1024 = 4,194,304; k 524,288; the two value
    # halves 524,288; conv 0 1280 x 3 = 3,840; conv 1 1280 x 257 = 328,960;
    # 2 temperatures -> 5,575,682; two norms 4,096; router 524,544 +
    # 131,584 + 256 + 4,096 = 660,480; beta 16; 8 experts x 3 x 2048 x 2048
    # = 100,663,296 -> 106,903,570 a layer (+ gamma in three of the four);
    # the tied table 131136 x 2048 = 268,566,528; the last norm 2048
    assert ZAYA.share_params(CONFIG) == (4 * 106_903_570 + 3 + 268_566_528
                                         + 2048)
    assert ZAYA.share_params(CONFIG) == 696_182_859
    assert round(ZAYA.share_params(CONFIG) * 16 / 1e9, 2) == 11.14
    assert round(ZAYA.share_params(CONFIG) * 16 / 2 ** 30, 2) == 10.37
    whole = dict(CONFIG, num_hidden_layers=40, num_experts=16,
                 vocab_size=262272)
    assert round(ZAYA.share_params(whole) / 1e9, 2) == 8.84


def test_the_model_builds_that_many_parameters():
    import jax
    family = ZAYA.build(CONFIG, TRAFFIC)
    shapes = jax.eval_shape(family.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 696_182_859
    p = shapes["params"]
    assert p["h0"]["moe"]["gate"].shape == (8, 2048, 2048)
    assert p["h0"]["moe"]["router"]["out"]["kernel"].shape == (256, 16)
    assert p["h0"]["attn_cca"]["conv1_kernel"].shape == (10, 2, 128, 128)
    assert p["h0"]["attn_cca"]["q_proj"]["kernel"].shape == (2048, 8, 128)
    assert p["wte"]["embedding"].shape == (131136, 2048)
    assert "lm_head" not in p and "gamma" not in p["h0"]["moe"]["router"]


def test_flops_per_token_counts_the_pair_that_lands_here():
    # a layer: q, o 4,194,304; k 524,288; v 524,288; conv 1 327,680; router
    # 524,288 + 131,072 + 4,096 = 659,456; half a pair x 3 x 2048 x 2048 =
    # 6,291,456 -> 12,521,472; the head 131136 x 2048 = 268,566,528
    # -> 6 x (4 x 12,521,472 + 268,566,528) = 1,911,914,496
    # attention: 4 layers x 12 x 16384 x 1024 / 2 = 402,653,184
    assert ZAYA.flops_per_token(CONFIG, 16384) == pytest.approx(
        1_911_914_496 + 402_653_184)


def test_kernel_work_at_the_cell_shape():
    work = ZAYA.flash_work(CONFIG, 16384, 1)
    assert work["cca_flash"]["flops"] == (4 * 14 * 8 * 128
                                          * 16384 * 16384 / 2)
    # a layer's bytes: q, o, dO, dQ (and o, q again) at 8 heads: 6 x
    # 33,554,432; k, v, dK, dV (and k, v again) at 2 heads: 6 x 8,388,608;
    # three float32 rows of 8 x 16384
    layer = 6 * 33_554_432 + 6 * 8_388_608 + 3 * 4 * 131_072
    assert work["cca_flash"]["bytes"] == 4 * layer
    assert work["flash"] == work["cca_flash"]
    rule = work["cca_flash"]["op_name_re"]
    for op in ("jit(step)/jvp(Zaya)/h0/attn_cca/pallas_call",
               "jit(step)/transpose(jvp(Zaya))/jvp(Zaya)/checkpoint/h3/"
               "attn_cca/pallas_call"):
        assert re.search(rule, op)
    assert not re.search(rule, "jvp(Zaya)/h0/attn_cca/bps.cca.mix/mul")
    moe = ZAYA.moe_work(CONFIG, 16384, 1)
    rows = 16384 // 2                                # 8,192 live rows
    assert moe["flops"] == 4 * 9 * 2 * rows * 2048 * 2048
    assert moe["bytes"] == 4 * 9 * 2 * (8 * 2048 * 2048 + rows * 4096)
    assert ZAYA.moe_work(CONFIG, 16384, 1, pair_share=0.25)["flops"] == (
        moe["flops"] / 2)


def _made_up_run(steps=2):
    """Two steps; per step and layer a 6 ms forward, its 6 ms
    recomputation and 7 + 6 ms of backward kernels (x 4 layers = 100 ms),
    twelve grouped matmuls of 1 ms, a gate kernel and a fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd, bwd = "jit(step)/jvp(Zaya)/{}", "jit(step)/transpose(jvp(Zaya))/{}"
    for _ in range(steps):
        for layer in range(4):
            scope = f"h{layer}/attn_cca/pallas_call"
            op(f"cca.f{layer}", 6, fwd.format(scope))
            op(f"cca.r{layer}", 6, bwd.format("checkpoint/" + scope))
            op(f"cca.k{layer}", 7, bwd.format(scope))
            op(f"cca.q{layer}", 6, bwd.format(scope))
        for i in range(12):
            op(f"gmm.{i}", 1, fwd.format(
                "h0/moe/bps.moe.experts/jit(gmm)/pallas_call"))
        op("gate.0", 2, fwd.format(
            "h0/moe/bps.moe.gate/jit(_gate_call)/bps_moe_gate/pallas_call"))
        op("fusion.9", 4)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    work = ZAYA.flash_work(CONFIG, 16384, 1)
    work["top1_moe"] = ZAYA.moe_work(CONFIG, 16384, 1)
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={"moe.held_pair_share": 0.4},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work=work, peaks=peaks_for("TPU v5 lite"),
        snap1={"gauges": {"head.logit_block_bytes": 1024 * 131136 * 4.0,
                          "head.logit_blocks": 16.0}},
        family=types.SimpleNamespace(
            experts_held=(0, 8),
            top1_moe_work=lambda seqs, pair_share: ZAYA.moe_work(
                CONFIG, 16384, seqs, pair_share=pair_share)),
        job=types.SimpleNamespace(seqs_per_chip=1))


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_readers_on_a_made_up_trace():
    run = _made_up_run()
    assert read("flash_ms", run) == pytest.approx(100.0)
    flash_s = 4 * 14 * 8 * 128 * 16384 * 16384 / 2 / 197e12  # compute roof
    assert read("cca_flash_roofline", run) == pytest.approx(
        100 * flash_s / 100e-3, rel=1e-6)
    assert run.info["cca_flash_roofline_bound"] == "compute"
    assert read("top1_moe_ms", run) == pytest.approx(12.0)  # not the gate
    moe_s = 4 * 9 * 2 * 8192 * 2048 * 2048 / 197e12
    assert read("top1_moe_roofline", run) == pytest.approx(
        100 * moe_s / 12e-3, rel=1e-6)
    assert run.info["top1_moe_roofline_bound"] == "compute"
    # the batch's own share (0.4, not the expected 0.5) rescales it
    assert run.info["top1_moe_roofline_pct_at_real_share"] == pytest.approx(
        100 * moe_s * 0.8 / 12e-3, rel=1e-6)
    assert read("top1_held_pair_share", run) == 0.4
    assert read("head_logit_block_GiB", run) == pytest.approx(
        1024 * 131136 * 4 / 2 ** 30)
    assert run.info["head.logit_blocks"] == 16.0


@pytest.mark.parametrize("name", ["cca_flash_roofline", "top1_moe_ms",
                                  "top1_moe_roofline"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # another family
    assert read(name, run) is None


def test_counter_readers_read_nothing_from_a_program_without_them():
    """A program that lacks the head's gauge or the share (another
    family's, or an older commit's under these benchmark files) gives
    nothing, and does not raise."""
    run = types.SimpleNamespace(snap1={"gauges": {}}, info={},
                                family=types.SimpleNamespace())
    assert read("head_logit_block_GiB", run) is None
    assert read("top1_held_pair_share", run) is None
    assert read("head_logit_block_GiB",
                types.SimpleNamespace(snap1={}, info={})) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    assert entry("workloads", CELL) == {
        "name": CELL, "config": "zaya1_8b", "traffic": "clm16384_fused",
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
                 "moe_held_pair_share"):
        assert CELL not in entry("per_layer", name)["workloads"]
        assert name not in reported
    # every older cell's metrics are what they were
    for cell in ("gpt2_medium.fused_1c", "olmoe_1b_7b.fused_1c",
                 "mellum2_12b.fused_1c"):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", cell)}


def test_the_new_entries_keep_the_contract_s_lengths():
    """A ``why`` and a ``source`` have 1 to 200 characters on one line."""
    for text in (entry("configs", "zaya1_8b")["why"],
                 entry("configs", "zaya1_8b")["source"],
                 entry("workloads", CELL)["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


# -------------------------------------- the gradient comparison's limits

@pytest.fixture(scope="module")
def toy():
    """The comparison of ``gradcheck_zaya.py`` on the rehearsal sizes in
    float32 (the toy's 256 tokens of width 64 in bfloat16 are noise; the
    chip's run is the bfloat16 one: PERF.md section 6 PR 31): the inputs
    and the reference's side, made once."""
    family, seqs = gradcheck_zaya.build(True, compute_dtype="float32")
    params, batch = gradcheck_zaya.inputs(family, seqs, 5)
    return params, batch, gradcheck_zaya.reference(family, params, batch)


def _program_side(toy, fault=None):
    params, batch, want = toy
    with (gradcheck_zaya.broken(fault) if fault
          else contextlib.nullcontext()):
        # built inside: new closures, so no jit cache outlives the break
        family, _ = gradcheck_zaya.build(True, compute_dtype="float32")
        return gradcheck_zaya.compare(family, params, batch, want)


def test_gradient_comparison_passes_on_the_toy(toy):
    out = _program_side(toy)
    assert out["ok"], (out["worst_leaf"], out["worst_rel_l2"])
    assert len(out["leaves"]) == 4 * 24 + 3 + 2
    assert max(out["worst_rel_l2"], out["worst_small_rel_l2"]) < 1e-3
    assert out["head_rel"] < 1e-7


@pytest.mark.parametrize("what", gradcheck_zaya.BREAKS)
def test_gradient_comparison_fails_each_deliberate_break(toy, what):
    """The chip's limits are tight enough: five of the faults move some
    gradient leaf of more than ``SMALL_LEAF`` numbers past ``GRAD_RTOL`` by
    a factor of 1.5 or more, and
    logits rounded to bfloat16 before the log-sum-exp — which neither the
    loss nor any gradient can see — move the head's own check past
    ``HEAD_RTOL`` (the chip's own readings: PERF.md section 6 PR 31)."""
    out = _program_side(toy, what)
    assert not out["ok"]
    if what == "logits_rounded_to_bf16":
        assert out["head_rel"] > 2 * gradcheck_zaya.HEAD_RTOL
        assert out["worst_rel_l2"] < gradcheck_zaya.GRAD_RTOL
    else:
        assert out["worst_rel_l2"] > 1.5 * gradcheck_zaya.GRAD_RTOL, out[
            "worst_leaf"]
    import byteps_tpu.models.gpt as gpt                  # undone on exit
    import byteps_tpu.models.zaya as model
    assert model.dropless_moe_mlp.__module__ == "byteps_tpu.parallel.expert"
    assert model.ZayaRouter.__call__.__name__ == "__call__"
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
    assert {"compiles_in_window", "top1_held_pair_share",
            "head_logit_block_GiB"} <= set(metrics)
    assert not {"cca_flash_roofline", "top1_moe_ms", "top1_moe_roofline",
                "flash_ms", "mfu_pct"} & set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    # 2 of 8 experts held: a quarter of the tokens, give or take the
    # random router's favourites
    assert 0.02 < metrics["top1_held_pair_share"]["value"] < 0.7
    # the toy's one block: 256 rows x 256 table rows x 4 B
    assert metrics["head_logit_block_GiB"]["value"] == pytest.approx(
        256 * 256 * 4 / 2 ** 30)


def test_without_a_tpu_the_cell_exits_at_once_with_no_line():
    p = _run_cell("--seed", "1", "--seconds", "1", "--trace", "0",
                  timeout=120)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "no TPU" in p.stderr
