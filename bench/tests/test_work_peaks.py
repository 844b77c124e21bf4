"""Hand counts of the work functions and the peaks table."""
import json
import os

import pytest

from bench import peaks, work
from bench.kinds.lm import model_dims

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen3_4b_counts():
    m = model_dims(_config("qwen3_4b"))
    assert work.lm_linear_params(m) == 3_633_315_840        # 3.63e9
    assert work.lm_head_params(m) * work.BF16 == 777_912_320  # 0.78 GB
    assert work.kv_bytes_per_token(m) == 147_456


def test_decode_step_bytes_and_ops():
    m = model_dims(_config("qwen3_4b"))
    ops, nbytes = work.decode_step_work(m, rows=64, live_positions=64 * 300)
    assert nbytes == pytest.approx(3.633e9 + 0.778e9 + 147_456 * 19_200,
                                   rel=1e-3)
    lin = 2 * (3_633_315_840 + 151_936 * 2560) * 64
    assert ops == pytest.approx(lin + 4 * 36 * 32 * 128 * 19_200)


def test_prefill_counts_real_tokens_only():
    m = model_dims(_config("qwen3_4b"))
    o1, _ = work.prefill_work(m, 100)
    o2, _ = work.prefill_work(m, 200)
    assert o2 > 1.9 * o1 - 2 * 151_936 * 2560


def test_resnet18_macs():
    # He et al. Table 1 (1.814 GMAC) with stage 2 at the served 55x55:
    # four 3x3x64x64 convs lose 56**2 - 55**2 = 111 output positions each
    layers = _config("resnet18")["layers"]
    assert work.cnn_macs(layers) == 1_814_073_344 - 4 * 111 * 9 * 64 * 64


def test_resnet18_table_sizes_chain():
    """Each layer's stated input is the previous layer's output."""
    h = block = 224
    for l in _config("resnet18")["layers"]:
        if l["kind"] in ("conv", "maxpool"):
            assert l["hin"] == (block if l["name"].endswith("_down") else h)
            if not l["name"].endswith("_down"):
                h = work.conv_out(h, l["hk"], l["stride"], l["pad"])
        if l["kind"] in ("maxpool", "add"):
            block = h


def test_v5e_peaks_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
