"""Model FLOPs per image from the configuration's shapes."""

import pytest

from chipbench.tests import tiny  # noqa: F401
from chipbench import flops, registry


def test_vig_ti_224_hand_count():
    conf = registry.config("vig_ti_iso")
    n, d = 196, 192
    block = 2 * n * d * d * (1 + 2 + 1 + 4 + 4) + 2 * n * n * d
    hand = 2 * n * (16 * 16 * 3) * d + 12 * block + 2 * d * 1000
    assert flops.per_image(conf, 224) == hand
    assert hand == pytest.approx(2.316e9, rel=1e-3)


def test_vig_s_pyr_896_counts_pooled_digc():
    conf = registry.config("vig_s_pyr")
    total = flops.per_image(conf, 896)
    digc0 = 2 * 50176 * 3136 * 80
    assert total == pytest.approx(2.45e11, rel=0.02)
    assert total > 2 * digc0
