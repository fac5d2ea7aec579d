"""The cell ``mellum2_12b.fused_1c`` (ISSUE 29): its configuration against
the published ``config.json``, the share's parameter count, the family's
operation counts against hand arithmetic, its six readers on a made-up
trace, its entries in BENCHMARK.json (found BY NAME), the gradient
comparison's tolerances against three deliberate breaks, and the
rehearsal's contract line."""

import json
import os
import subprocess
import sys
import types

import pytest

from harness import spec, xplane
from harness.peaks import peaks_for

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import gradcheck_mellum  # noqa: E402

CELL = "mellum2_12b.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
MELLUM = spec.load_module("families", "mellum")
NEW = ["swa_flash_ms", "swa_flash_roofline", "swa_visited_block_share",
       "held_moe_ms", "held_moe_roofline", "moe_held_pair_share"]

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# JetBrains/Mellum2-12B-A2.5B-Instruct config.json (the guide's catalog row)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def entry(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


def test_configuration_is_the_published_one_but_for_the_share():
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert differs == set(CONFIG["reduced"]) == set(
        entry("configs", "mellum2_12b")["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 16, 24576)
    # the published counts stand beside the cut ones
    assert CONFIG["num_routed_experts"] == 64
    assert CONFIG["vocab_size_published"] == 98304
    assert CONFIG["experts_held"] == [0, 16]
    assert CONFIG["layer_types"][:4] == PERIOD        # one whole period
    assert {"qk_norm", "rotary_pairing", "router_aux_loss_coef", "mtp_head",
            "training_length", "dtypes", "weights", "data"} <= set(
                CONFIG["assumed"])
    for said in ("7 pipeline stages", "4 chips that share each layer",
                 "595.2 M parameters", "9.52 GB"):
        assert said in CONFIG["deployment"]
    assert entry("configs", "mellum2_12b")["source"] == CONFIG["source"]
    assert TRAFFIC["seq_len"] == CONFIG["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]
    # the router loss is of the chip's whole shard: so is the reference's
    assert TRAFFIC["reference_microbatch"] == TRAFFIC["seqs_per_chip"] == 2
    for figure in ("18.72", "4.23", "3.15"):           # the three peaks
        assert figure in TRAFFIC["notes"]


def test_the_share_is_595_million_parameters():
    # attention 2304 x (4096 + 512 + 512) + 4096 x 2304 = 21,233,664;
    # router 2304 x 64 = 147,456; 16 experts x 3 x 2304 x 896 = 99,090,432;
    # norms 2 x 2304 + 2 x 128 -> 120,476,416 a layer; embedding + head
    # 2 x 24576 x 2304 = 113,246,208; the last norm 2304
    assert MELLUM.share_params(CONFIG) == 4 * 120_476_416 + 113_246_208 + 2304
    assert MELLUM.share_params(CONFIG) == 595_154_176
    assert round(MELLUM.share_params(CONFIG) / 1e6, 1) == 595.2
    assert round(MELLUM.share_params(CONFIG) * 16 / 1e9, 2) == 9.52
    whole = dict(CONFIG, num_hidden_layers=28, num_experts=64,
                 vocab_size=98304)
    assert round(MELLUM.share_params(whole) / 1e9, 2) == 12.15


def test_the_model_builds_that_many_parameters():
    import jax
    family = MELLUM.build(CONFIG, TRAFFIC)
    shapes = jax.eval_shape(family.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 595_154_176
    moe = shapes["params"]["h0"]["moe"]
    assert moe["router"].shape == (2304, 64)
    assert moe["gate"].shape == (16, 2304, 896)
    assert set(shapes["params"]["h2"]) >= {"attn_swa"}
    assert set(shapes["params"]["h3"]) >= {"attn"}
    assert shapes["params"]["lm_head"]["kernel"].shape == (2304, 24576)


def test_flops_per_token_counts_the_pairs_that_land_here():
    # a layer: 21,233,664 + 147,456 + 2 x 6,193,152 (2 of a token's 8
    # pairs in expectation) = 33,767,424; head 2304 x 24576 = 56,623,104
    # -> 6 x (4 x 33,767,424 + 56,623,104) = 1,150,156,800
    # attention: full 12 x 8192 x 4096 / 2 = 201,326,592; a sliding
    # layer sees (1024 x 1025 / 2 + 7168 x 1024) / 8192 = 960.0625 keys a
    # row: 12 x 4096 x 960.0625 = 47,188,992
    assert MELLUM.band_keys(8192, 1024) == 524_800 + 7_340_032
    assert MELLUM.flops_per_token(CONFIG, 8192) == pytest.approx(
        1_150_156_800 + 201_326_592 + 3 * 47_188_992)


def test_kernel_work_at_the_cell_shape():
    work = MELLUM.flash_work(CONFIG, 8192, 2)
    band = 2 * 32 * 128 * 7_864_832                  # scores x head_dim
    assert work["swa_flash"]["flops"] == 3 * 14 * band
    assert work["full_flash"]["flops"] == 14 * 2 * 32 * 128 * 8192 * 8192 / 2
    assert work["flash"]["flops"] == (work["swa_flash"]["flops"]
                                      + work["full_flash"]["flops"])
    # a layer's bytes: q, o, dO, dQ (and o, q again) at 32 heads: 6 x
    # 134,217,728; k, v, dK, dV (and k, v again) at 4 heads: 6 x
    # 16,777,216; three float32 rows of 2 x 32 x 8192
    layer = 6 * 134_217_728 + 6 * 16_777_216 + 3 * 4 * 524_288
    assert work["swa_flash"]["bytes"] == 3 * layer
    assert work["full_flash"]["bytes"] == layer
    import re
    assert re.search(work["swa_flash"]["op_name_re"],
                     "jit(step)/jvp(Mellum)/h0/attn_swa/pallas_call")
    assert not re.search(work["swa_flash"]["op_name_re"],
                         "jit(step)/jvp(Mellum)/h3/attn/pallas_call")
    for op in ("jvp(Mellum)/h0/attn_swa/pallas_call",
               "transpose(jvp(Mellum))/h3/attn/pallas_call"):
        assert re.search(work["flash"]["op_name_re"], op)
    moe = MELLUM.moe_work(CONFIG, 8192, 2)
    rows = 2 * 8192 * 8 // 4                         # 32,768 live rows
    assert moe["flops"] == 4 * 9 * 2 * rows * 2304 * 896
    assert moe["bytes"] == 4 * 9 * 2 * (16 * 2304 * 896 + rows * 3200)
    assert MELLUM.moe_work(CONFIG, 8192, 2, pair_share=0.5)["flops"] == (
        2 * moe["flops"])


def _made_up_run(steps=2):
    """Two steps; per step: per sliding layer a 2 ms forward, its 2 ms
    recomputation and 3 + 2 ms of backward kernels (x 3 layers = 27 ms),
    the full layer's 6 + 6 + 9 + 8 = 29 ms, twelve grouped matmuls of 1 ms
    and a fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = [0.0]

    def op(name, ms, op_name=None):
        if op_name:
            mosaic[name] = op_name
        trace.ops[0].append((name, t[0], t[0] + ms * 1e6))
        t[0] += ms * 1e6

    fwd, bwd = "jit(step)/jvp(Mellum)/{}", "jit(step)/transpose(jvp(Mellum))/{}"
    for _ in range(steps):
        for layer in range(3):
            scope = f"h{layer}/attn_swa/pallas_call"
            op(f"swa.f{layer}", 2, fwd.format(scope))
            op(f"swa.r{layer}", 2, bwd.format("rematted_computation/"
                                              + scope))
            op(f"swa.k{layer}", 3, bwd.format(scope))
            op(f"swa.q{layer}", 2, bwd.format(scope))
        for name, ms, where in (("full.f", 6, fwd), ("full.r", 6, bwd),
                                ("full.k", 9, bwd), ("full.q", 8, bwd)):
            op(name, ms, where.format("h3/attn/pallas_call"))
        for i in range(12):
            op(f"gmm.{i}", 1, fwd.format(
                "h0/moe/bps.moe.experts/jit(gmm)/pallas_call"))
        op("fusion.9", 4)
    trace.host.append(("bench.traced_window", 0.0, t[0]))
    work = MELLUM.flash_work(CONFIG, 8192, 2)
    work["held_moe"] = MELLUM.moe_work(CONFIG, 8192, 2)
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={"moe.held_pair_share": 0.2},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work=work, peaks=peaks_for("TPU v5 lite"),
        snap1={"gauges": {"flash.visited_block_share.window": 45 / 256}},
        family=types.SimpleNamespace(
            experts_held=(0, 16),
            held_moe_work=lambda seqs, pair_share: MELLUM.moe_work(
                CONFIG, 8192, seqs, pair_share=pair_share)),
        job=types.SimpleNamespace(seqs_per_chip=2))


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_readers_on_a_made_up_trace():
    run = _made_up_run()
    assert read("swa_flash_ms", run) == pytest.approx(27.0)
    assert read("flash_ms", run) == pytest.approx(56.0)   # both scopes
    assert read("held_moe_ms", run) == pytest.approx(12.0)
    band_s = 3 * 14 * 2 * 32 * 128 * 7_864_832 / 197e12   # compute roof
    assert read("swa_flash_roofline", run) == pytest.approx(
        100 * band_s / 27e-3, rel=1e-6)
    assert run.info["swa_flash_roofline_bound"] == "compute"
    assert run.info["full_flash_ms"] == pytest.approx(29.0)
    full_s = 14 * 2 * 32 * 128 * 8192 * 8192 / 2 / 197e12
    assert run.info["full_flash_roofline_pct"] == pytest.approx(
        100 * full_s / 29e-3, rel=1e-6)
    moe_s = 4 * 9 * 2 * 32768 * 2304 * 896 / 197e12
    assert read("held_moe_roofline", run) == pytest.approx(
        100 * moe_s / 12e-3, rel=1e-6)
    assert run.info["held_moe_roofline_bound"] == "compute"
    # the batch's own share (0.2, not the expected 0.25) rescales it
    assert run.info["held_moe_roofline_pct_at_real_share"] == pytest.approx(
        100 * moe_s * 0.8 / 12e-3, rel=1e-6)
    assert read("swa_visited_block_share", run) == pytest.approx(0.17578125)
    assert read("moe_held_pair_share", run) == 0.2


@pytest.mark.parametrize("name", ["swa_flash_ms", "swa_flash_roofline",
                                  "held_moe_ms", "held_moe_roofline"])
def test_trace_readers_read_nothing_without_a_trace_or_their_kernels(name):
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(name, run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # another family
    assert read(name, run) is None


def test_counter_readers_read_nothing_from_a_program_without_them():
    """A program that lacks the window gauge or the share (the parent
    commit's, under this PR's benchmark files) gives nothing, and does
    not raise."""
    run = types.SimpleNamespace(snap1={"gauges": {}}, info={},
                                family=types.SimpleNamespace())
    assert read("swa_visited_block_share", run) is None
    assert read("moe_held_pair_share", run) is None


def test_the_new_entries_are_found_by_name_and_match_their_files():
    assert entry("workloads", CELL) == {
        "name": CELL, "config": "mellum2_12b", "traffic": "clm8192_fused",
        "chips": 1, "why": entry("workloads", CELL)["why"]}
    assert len(entry("workloads", CELL)["why"]) <= 200
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
                 "flash_roofline"):
        assert CELL not in entry("per_layer", name)["workloads"]
        assert name not in reported
    # every older cell's metrics are what they were
    for cell in ("gpt2_medium.fused_1c", "olmoe_1b_7b.fused_1c"):
        assert not set(NEW) & {m["name"] for m in spec.metrics_for(
            BENCH, "per_layer", cell)}


# ----------------------------------- the gradient comparison's tolerances

def _toy_comparison():
    """The comparison of ``gradcheck_mellum.py`` on the rehearsal sizes in
    float32 (the toy's 256 tokens of width 64 in bfloat16 are noise; the
    chip's run is the bfloat16 one: PERF.md section 6 PR 29)."""
    return gradcheck_mellum.run(5, rehearsal=True, compute_dtype="float32")


def test_gradient_comparison_passes_on_the_toy():
    out = _toy_comparison()
    assert out["ok"], (out["worst_leaf"], out["worst_rel_l2"])
    assert len(out["leaves"]) == 4 * 12 + 3 and out["worst_rel_l2"] < 1e-3


@pytest.mark.parametrize("what", gradcheck_mellum.BREAKS)
def test_gradient_comparison_fails_each_deliberate_break(what):
    """The chip's tolerances (``GRAD_RTOL``, ``LOGIT_RTOL``) are tight
    enough: a window one (toy: 32-key) sub-block too wide, unrenormalised
    weights and a missing ``attention_factor`` each move some gradient
    leaf past ``GRAD_RTOL`` by a factor of 1.5 or more (the chip's own
    readings of the three: PERF.md section 6 PR 29)."""
    with gradcheck_mellum.broken(what, window_by=32):
        out = _toy_comparison()
    assert not out["ok"]
    assert out["worst_rel_l2"] > 1.5 * gradcheck_mellum.GRAD_RTOL, out[
        "worst_leaf"]
    import byteps_tpu.models.mellum as model
    import byteps_tpu.ops as ops
    from byteps_tpu.ops.flash_attention import flash_attention
    assert ops.flash_attention is flash_attention      # undone on exit
    assert model.dropless_moe_mlp.__module__ == "byteps_tpu.parallel.expert"


def test_rehearsal_prints_the_contract_line_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearsal"], cwd=spec.CHECKOUT, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["rehearsal"] is True
    metrics = line["metrics"]
    # counts only on the CPU: never a device metric
    assert set(metrics) == {"engine_dispatches_per_step",
                            "engine_sync_stall_ms", "compiles_in_window",
                            "swa_visited_block_share", "moe_held_pair_share"}
    assert metrics["compiles_in_window"]["value"] == 0
    # 2 of 8 experts held: a quarter of the pairs, give or take the
    # random router's favourites
    assert 0.05 < metrics["moe_held_pair_share"]["value"] < 0.6
    assert 0 < metrics["swa_visited_block_share"]["value"] <= 1.0


def test_the_new_entries_keep_the_contract_s_lengths():
    """A ``why`` and a ``source`` have 1 to 200 characters on one line."""
    for text in (entry("configs", "mellum2_12b")["why"],
                 entry("configs", "mellum2_12b")["source"],
                 entry("workloads", CELL)["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
