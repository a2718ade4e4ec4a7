"""The FLOP functions against two counts made by hand (ISSUE 24)."""

import json
import os

import pytest

from bench import families

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _sizes(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_gpt2_small_at_1024():
    sizes = _sizes("gpt2_small")
    fam = families.load(sizes["family"])
    # 12 layers x 12 d^2 + V d = 84,934,656 + 38,597,376 matmul parameters
    assert fam.matmul_params(sizes) == 123_532_032
    # 6 x 123.5 M + 6 x 12 x 1024 x 768 = 741.2 M + 56.6 M
    assert fam.flops_per_token(sizes, 1024) == pytest.approx(797.7e6, rel=2e-4)
    assert fam.flops_per_token(sizes, 256) == pytest.approx(755.3e6, rel=2e-4)


def test_mistral_7b_eight_layers_at_8192():
    sizes = _sizes("mistral_7b_l8")
    fam = families.load(sizes["family"])
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert fam.matmul_params(sizes) == 8 * per_layer + 32768 * 4096
    # 6 x 1.879 G + 6 x 8 x 8192 x 4096 = 11.27 G + 1.61 G
    assert fam.flops_per_token(sizes, 8192) == pytest.approx(12.88e9, rel=1e-3)


def test_ledger_mfu_follows_from_the_count():
    """PR 23's ledger line: 90,442 tokens/s at T=256 is 34.68% of 197 TFLOP/s."""
    sizes = _sizes("gpt2_small")
    fam = families.load(sizes["family"])
    assert 100 * 90442 * fam.flops_per_token(sizes, 256) / 197e12 == pytest.approx(34.68, abs=0.01)
