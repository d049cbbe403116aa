"""``model_flops`` against counts made by hand from the published shapes."""

import pytest

from benchmark.models import gpt2, resnet
from benchmark.tests.conftest import load_config


def resnet50_macs_by_hand():
    """Multiply-accumulates of one 224x224 image, forward, written out
    stage by stage (v1.5: a down-sampling block's first 1x1 still runs at
    the incoming resolution)."""
    stem = 112 * 112 * 7 * 7 * 3 * 64
    total = stem
    # (incoming resolution, outgoing resolution, c_in, width, blocks)
    for hw_in, hw, c_in, f, blocks in ((56, 56, 64, 64, 3),
                                       (56, 28, 256, 128, 4),
                                       (28, 14, 512, 256, 6),
                                       (14, 7, 1024, 512, 3)):
        first = (hw_in ** 2 * c_in * f            # 1x1 reduce
                 + hw ** 2 * 9 * f * f            # 3x3 (strided)
                 + hw ** 2 * f * 4 * f            # 1x1 expand
                 + hw ** 2 * c_in * 4 * f)        # projection shortcut
        rest = hw ** 2 * (4 * f * f + 9 * f * f + f * 4 * f)
        total += first + (blocks - 1) * rest
    return total + 2048 * 1000, stem


def test_resnet50_against_hand_count():
    config = load_config("resnet50")
    macs, stem = resnet50_macs_by_hand()
    assert macs == pytest.approx(4.09e9, rel=0.01)   # the known figure
    assert len(resnet.conv_table(config)) == 53 + 1
    # forward + two backward passes, less the stem's input gradient
    want = 2 * (3 * macs - stem)
    assert resnet.model_flops(config, 1) == want
    assert resnet.model_flops(config, 256) == 256 * want


def test_gpt2_medium_against_hand_count():
    config = load_config("gpt2-medium")
    per_layer = 3 * 1024 * 1024 + 1024 * 1024 + 2 * 1024 * 4096
    n = 24 * per_layer + 1024 * 50257
    assert n == 353_453_056
    assert gpt2.matmul_params(config) == n
    per_token = 6 * n + 12 * 24 * 16 * 64 * 1024
    assert per_token == 2_422_708_224
    assert gpt2.model_flops(config, 4, 1024) == per_token * 4 * 1024
    # attention grows with the sequence, the rest does not
    assert (gpt2.model_flops(config, 1, 2048) / 2048
            - gpt2.model_flops(config, 1, 1024) / 1024
            == 12 * 24 * 16 * 64 * 1024)
