"""``flops.py`` against counts worked by hand for both configurations."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import flops  # noqa: E402


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,multiplying,total", [
    # 12 layers x 12 x 768^2 + 768 x 50257 ; every leaf of the program's init
    ("gpt2-small", 12 * 12 * 768 * 768 + 768 * 50257, 163_050_577),
    ("gpt2-medium", 24 * 12 * 1024 * 1024 + 1024 * 50257, 406_238_289),
])
def test_parameter_counts(name, multiplying, total):
    c = cfg(name)
    assert flops.multiplying_params(c) == multiplying
    assert c["parameters_as_run"] == total


def test_small_training_step_by_hand():
    c = cfg("gpt2-small")
    n = 123_532_032                      # 84,934,656 + 38,597,376
    assert flops.multiplying_params(c) == n
    tokens = 8 * 1024
    dense = 6 * n * tokens               # 6.07e12
    # one causal matmul of one layer: 8 rows x 2 x 1024^2 x 768 / 2
    one = 8 * 1024 * 1024 * 768
    assert flops.attention_matmul_flops(c, 8, 1024) == one
    assert flops.train_flops(c, 8, 1024) == dense + 12 * 6 * one
    assert flops.train_flops(c, 8, 1024) == pytest.approx(6.536e12, rel=1e-3)


def test_medium_scoring_request_by_hand():
    c = cfg("gpt2-medium")
    n = 24 * 12 * 1024 * 1024 + 1024 * 50257
    want = 2 * n * 300 + 24 * 2 * (300 * 300 * 1024)
    assert flops.score_flops(c, 300) == want


def test_attention_layer_bytes_and_bound():
    c = cfg("gpt2-small")
    elems = 8 * 1024 * 768
    fwd = flops.attention_layer(c, 8, 1024, backward=False)
    assert fwd["bytes"] == 3 * elems * 2 + elems * 4
    assert fwd["flops"] == 2 * 8 * 1024 * 1024 * 768
    both = flops.attention_layer(c, 8, 1024, backward=True)
    assert both["flops"] == 6 * 8 * 1024 * 1024 * 768
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 36 bytes an element against 6144 operations: at 1024 tokens and
    # head_dim 64 the float32 outputs make even attention memory-bound
    assert both["bytes"] == 36 * elems
    secs, bound = flops.least_seconds(both, peaks)
    assert bound == "memory" and secs == pytest.approx(36 * elems / 819e9)
    assert flops.least_seconds(fwd, peaks)[1] == "memory"


def test_adam_is_memory_bound_at_28_bytes_a_parameter():
    need = flops.adam(163_050_577)
    assert need["bytes"] == 28 * 163_050_577
    secs, bound = flops.least_seconds(
        need, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and secs == pytest.approx(5.574e-3, rel=1e-3)
