"""The cell ``olmoe_1b_7b.fused_1c`` (ISSUE 25): its configuration against
the published widths, the family's operation counts against hand
arithmetic, its three readers on a made-up trace, its entries in
BENCHMARK.json, and the rehearsal's contract line."""

import json
import os
import subprocess
import sys
import types

import pytest

from harness import spec, xplane
from harness.peaks import peaks_for

CELL = "olmoe_1b_7b.fused_1c"
BENCH = spec.load_benchmark()
FOUND = spec.resolve(BENCH, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
OLMOE = spec.load_module("families", "olmoe")
NEW = ["moe_ms", "moe_roofline", "moe_load_max_over_mean"]

# allenai/OLMoE-1B-7B-0125-Instruct config.json (the guide's catalog row)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_configuration_is_the_published_one_but_for_depth():
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "?") != v}
    assert differs == {"num_hidden_layers"} == set(CONFIG["reduced"])
    entry = [c for c in BENCH["configs"] if c["name"] == "olmoe_1b_7b"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 1
    assert {"qk_norm", "router_aux_loss_coef", "router_z_loss_coef",
            "weights", "data"} <= set(CONFIG["assumed"])
    assert TRAFFIC["seq_len"] == CONFIG["max_position_embeddings"]
    # the router losses are of the chip's whole shard: so is the reference's
    assert TRAFFIC["reference_microbatch"] == TRAFFIC["seqs_per_chip"]


def test_flops_per_token_counts_the_eight_active_experts():
    # one layer: q,k,v,o 4 x 2048^2 = 16,777,216; 8 experts x 3 x 2048 x
    # 1024 = 50,331,648; router 2048 x 64 = 131,072 -> 67,239,936
    # head 2048 x 50304 = 103,022,592 -> 170,262,528; x 6 = 1,021,575,168
    # causal attention 12 x 4096 x 2048 / 2 = 50,331,648 (one layer)
    assert OLMOE.flops_per_token(CONFIG, 4096) == pytest.approx(
        1_021_575_168 + 50_331_648)
    # all 64 experts would be 402,653,184 a layer: 3.1 x the figure
    sixteen = dict(CONFIG, num_hidden_layers=16)
    assert OLMOE.flops_per_token(sixteen, 4096) == pytest.approx(
        6 * (16 * 67_239_936 + 103_022_592) + 16 * 50_331_648)


def test_moe_work_at_the_cell_shape():
    work = OLMOE.moe_work(CONFIG, 4096, 4)
    rows = 4 * 4096 * 8                              # 131,072 pair rows
    one = 2 * rows * 2048 * 1024                     # 549,755,813,888
    assert work["flops"] == 9 * one == 4_947_802_324_992
    # a pass of one matmul: 64 matrices of 2048 x 1024 + the rows in and
    # out, bf16: 2 x (134,217,728 + 131,072 x 3072) = 1,073,741,824 B
    assert work["bytes"] == 9 * 1_073_741_824
    # at the issue's 8,192 tokens: 2.47 TFLOP a step
    assert OLMOE.moe_work(CONFIG, 4096, 2)["flops"] == pytest.approx(
        2.474e12, rel=1e-3)
    flash = OLMOE.flash_work(CONFIG, 4096, 4)        # head size 128
    bhttd = 4 * 16 * 4096 * 4096 * 128
    assert flash["flops"] == (4 + 10) * bhttd / 2


def _made_up_run(steps=2):
    """Two steps; per step three forward kernels of 2 ms, three row
    gradients of 2 ms, three matrix gradients of 3 ms (21 ms), two flash
    kernels (5 ms) and a fusion."""
    trace = xplane.Trace()
    mosaic = {}
    t = 0.0
    scope = "jit(step)/{}/h0/moe/bps.moe.experts/jit({})/pallas_call"
    for s in range(steps):
        for i, (kind, ms, wrap) in enumerate(
                [("gmm", 2, "jvp(Olmoe)")] * 3
                + [("gmm", 2, "transpose(jvp(Olmoe))")] * 3
                + [("tgmm", 3, "transpose(jvp(Olmoe))")] * 3):
            name = f"{kind}.{i}"
            mosaic[name] = scope.format(wrap, kind)
            trace.ops[0].append((name, t, t + ms * 1e6))
            t += ms * 1e6
        for name, ms in (("attn.3", 2), ("attn.4", 3)):
            mosaic[name] = "jit(step)/jvp(Olmoe)/h0/attn/pallas_call"
            trace.ops[0].append((name, t, t + ms * 1e6))
            t += ms * 1e6
        trace.ops[0].append(("fusion.9", t, t + 4e6))
        t += 4e6
    trace.host.append(("bench.traced_window", 0.0, t))
    return types.SimpleNamespace(
        trace=trace, mosaic=mosaic, info={},
        window=types.SimpleNamespace(traced_steps=steps),
        kernel_work={"moe": OLMOE.moe_work(CONFIG, 4096, 4),
                     "flash": OLMOE.flash_work(CONFIG, 4096, 4)},
        peaks=peaks_for("TPU v5 lite"))


def test_moe_readers_on_a_made_up_trace():
    run = _made_up_run()
    ms = spec.load_module("layer_metrics", "moe_ms").read(run)
    assert ms == pytest.approx(21.0)
    share = spec.load_module("layer_metrics", "moe_roofline").read(run)
    # compute roof: 4,947,802,324,992 / 197e12 = 25.116 ms (memory: 9.66
    # GB / 819e9 = 11.8 ms) over 21 ms
    assert share == pytest.approx(100 * 25.1157 / 21.0, rel=1e-4)
    assert run.info["moe_roofline_bound"] == "compute"
    # the flash share at head size 128 rides the info line
    flash_s = 14 * 4 * 16 * 4096 * 4096 * 128 / 2 / 197e12
    assert run.info["flash_roofline_pct"] == pytest.approx(
        100 * flash_s / 5e-3, rel=1e-6)


@pytest.mark.parametrize("name", ["moe_ms", "moe_roofline"])
def test_trace_readers_read_nothing_without_a_trace_or_an_expert_layer(name):
    read = spec.load_module("layer_metrics", name).read
    run = _made_up_run()
    run.trace = None                                 # an unreadable trace
    assert read(run) is None
    run = _made_up_run()
    run.kernel_work = {}                             # a dense family
    assert read(run) is None


def test_load_reader_reads_nothing_from_a_family_without_experts():
    read = spec.load_module("layer_metrics", "moe_load_max_over_mean").read
    assert read(types.SimpleNamespace(family=types.SimpleNamespace())) is None


def test_the_new_entries_follow_the_older_ones_and_match_their_files():
    """Appended after everything PR 24's benchmark had, in this order —
    by position relative to the older entries, not by "last": the next
    PR appends after these."""
    def names(section):
        return [entry["name"] for entry in BENCH[section]]

    assert names("configs").index("olmoe_1b_7b") == 2
    assert names("workloads").index(CELL) == 4
    assert BENCH["workloads"][4] == {
        "name": CELL, "config": "olmoe_1b_7b", "traffic": "clm4096_fused",
        "chips": 1, "why": BENCH["workloads"][4]["why"]}
    first = names("per_layer").index("engine_assemble_ms") + 1
    assert names("per_layer")[first:first + len(NEW)] == NEW
    for m in BENCH["per_layer"][first:first + len(NEW)]:
        reader = spec.load_module("layer_metrics", m["name"])
        assert m["workloads"] == [CELL]
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (m["unit"], m["better"], m["source"],
                                  m["layer"], m["moves"])
    assert "flash_ms" in {m["name"] for m in
                          spec.metrics_for(BENCH, "per_layer", CELL)}


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
                            "moe_load_max_over_mean"}
    assert metrics["compiles_in_window"]["value"] == 0
    # 8 experts, top-2, 256 tokens: some expert is over the mean, none
    # can hold more than every token (E / k = 4 x the mean)
    assert 1.0 <= metrics["moe_load_max_over_mean"]["value"] <= 4.0
