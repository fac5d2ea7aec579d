"""FLOPs / bytes functions against cases worked by hand, and the peaks."""

import pytest

from harness import flops as F
from harness import spec
from harness.peaks import peaks_for


def _config(name):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")


def test_bert_large_seq128_flops_per_token():
    bert = spec.load_module("families", "bert")
    # one layer: q,k,v,out 4 x 1024^2 = 4,194,304; MLP 2 x 1024 x 4096 =
    # 8,388,608 -> 12,582,912; x 24 = 301,989,888
    # head on 19 of 128 positions: (1024^2 + 1024 x 30528) x 19/128
    #   = 32,309,248 x 0.1484375 = 4,795,904
    # 6 x (301,989,888 + 4,795,904) = 1,840,714,752
    # attention: 12 x 128 x 1024 = 1,572,864 a layer, x 24 = 37,748,736
    assert bert.n_masked(128, 0.15) == 19
    assert bert.flops_per_token(_config("bert_large"), 128, 0.15) == \
        pytest.approx(1_840_714_752 + 37_748_736)


def test_gpt2_medium_seq1024_flops_per_token():
    gpt = spec.load_module("families", "gpt")
    # blocks 301,989,888 + head 1024 x 50304 = 51,511,296 -> 353,501,184
    # 6 x = 2,121,007,104; causal attention 12 x 1024 x 1024 / 2 =
    # 6,291,456 a layer, x 24 = 150,994,944
    assert gpt.flops_per_token(_config("gpt2_medium"), 1024) == \
        pytest.approx(2_121_007_104 + 150_994_944)


def test_flash_work_at_the_cell_shape():
    # b 8, h 16, t 1024, d 64, causal, bf16
    fwd = F.flash_forward(8, 16, 1024, 64, causal=True)
    bwd = F.flash_backward(8, 16, 1024, 64, causal=True)
    bhttd = 8 * 16 * 1024 * 1024 * 64          # 8,589,934,592
    assert fwd["flops"] == 2 * bhttd           # 2 matmuls x 2, halved
    assert bwd["flops"] == 5 * bhttd           # 5 matmuls x 2, halved
    bhtd = 8 * 16 * 1024 * 64                  # 8,388,608 elements
    assert fwd["bytes"] == 4 * bhtd * 2 + 4 * 8 * 16 * 1024
    assert bwd["bytes"] == 8 * bhtd * 2 + 8 * 8 * 16 * 1024
    gpt = spec.load_module("families", "gpt")
    work = gpt.flash_work(_config("gpt2_medium"), 1024, 8)
    assert work["flops"] == 24 * 7 * bhttd
    assert work["bytes"] == 24 * (fwd["bytes"] + bwd["bytes"])


def test_roofline_says_which_roof_binds():
    peaks = peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    r = F.roofline(197e12, 1.0, peaks)
    assert r == {"seconds": pytest.approx(1.0), "bound": "compute"}
    r = F.roofline(1.0, 819e9 * 2, peaks)
    assert r == {"seconds": pytest.approx(2.0), "bound": "memory"}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")
