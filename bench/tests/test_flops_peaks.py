"""Model FLOP counts and the peaks table."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import flops, peaks  # noqa: E402


def _qwen3(layers):
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen3-1.7b-d8.json")) as f:
        model = dict(json.load(f)["model"], num_hidden_layers=layers)
    return flops.lm_shapes(model)


def test_qwen3_hand_count():
    # by hand, per layer: q, o 2048 x 2048; k, v 2048 x 1024; MLP 3 x 2048
    # x 6144 = 50,331,648; the tied head 151,936 x 2048 = 311,164,928
    p4 = flops.matmul_params(_qwen3(4))
    assert p4["head"] == 311_164_928
    assert p4["layers"] == 4 * 50_331_648
    assert p4["total"] == 512_491_520            # 0.5125 B
    assert p4["head"] / p4["total"] == pytest.approx(0.607, abs=1e-3)
    p8 = flops.matmul_params(_qwen3(8))
    assert p8["head"] / p8["total"] == pytest.approx(0.436, abs=1e-3)


def test_flops_per_token():
    s = _qwen3(8)
    attn = 6 * 8 * 16 * 128 * 1024                # causal: half the pairs
    assert flops.train_flops_per_token(s, 1024) == \
        6 * flops.matmul_params(s)["total"] + attn
    assert attn / flops.train_flops_per_token(s, 1024) == \
        pytest.approx(0.023, abs=1e-3)


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
