"""Model FLOPs from shapes, against counts by hand, and the peaks table."""
import json

import pytest

from bench import flops, harness
from bench_fixtures import REPO


def _model(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


def test_qwen2_counts_by_hand():
    m = _model("qwen2-0.5b")
    # per layer: q 896x896, k and v 896x128, o 896x896, MLP 3 x 896x4864
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert layer == 14_909_440
    # 24 layers, and the tied head counted once: 896 x 151936
    assert flops.matmul_params(m) == 24 * layer + 896 * 151_936 == 493_961_216
    # causal attention at S 1024: 3 x 2 * 1024 * 14 * 64 a layer and token
    attn = 24 * 3 * 2 * 1024 * 14 * 64
    assert flops.train_flops_per_token(m, 1024) == 6 * 493_961_216 + attn
    assert flops.train_flops_per_token(m, 1024) == pytest.approx(3.10e9, rel=2e-3)


def test_danube_stage_counts_by_hand():
    m = _model("h2o-danube-1.8b")
    # per layer: q 2560x2560, k and v 2560x640, o 2560x2560, MLP 3 x 2560x6912
    layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert layer == 69_468_160
    # 4 layers and an untied head 2560 x 32000; the embedding gather counts none
    assert flops.matmul_params(m) == 4 * layer + 2560 * 32_000 == 359_792_640
    # the 4096 window is longer than S 2048, so attention is plainly causal
    attn = 4 * 3 * 2 * 2048 * 32 * 80
    assert flops.train_flops_per_token(m, 2048) == 6 * 359_792_640 + attn
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(2.29e9, rel=3e-3)


def test_window_shorter_than_sequence_cuts_attention():
    m = dict(_model("h2o-danube-1.8b"), sliding_window=1024)
    # rows see on average W (1 - W / 2S) = 1024 * 7/8 = 896 keys at S 4096
    assert flops.attention_flops_per_token(m, 4096) == 4 * 3 * 2 * 2 * 896 * 32 * 80


def test_peaks_by_device_kind():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v9 imaginary")
