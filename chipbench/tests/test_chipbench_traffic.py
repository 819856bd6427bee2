"""Traffic generators: the same seed gives the same requests, and every
seed gives the same amount of work."""

import numpy as np
import pytest

from chipbench.tests import tiny  # noqa: F401  (puts the checkout on sys.path)
from chipbench import drive, registry

BIG = 2 ** 40 + 12345  # seeds run past 32 bits


def test_poisson_same_seed_same_schedule():
    kind = registry.module("traffic", "poisson")
    mix = registry.traffic("poisson_448")
    a = kind.schedule(mix, BIG, 10.0)
    b = kind.schedule(mix, BIG, 10.0)
    np.testing.assert_array_equal(a["due"], b["due"])
    np.testing.assert_array_equal(a["image"], b["image"])


def test_poisson_seeds_share_the_arrivals_not_the_images():
    kind = registry.module("traffic", "poisson")
    mix = dict(registry.traffic("poisson_448"), rate_per_s=50.0)
    a = kind.schedule(mix, 1, 10.0)
    b = kind.schedule(mix, BIG, 10.0)
    assert len(a["due"]) == len(b["due"]) == 500
    np.testing.assert_array_equal(a["due"], b["due"])
    assert not np.array_equal(a["image"], b["image"])
    for s in (a, b):
        assert s["due"][0] == 0.0 and s["due"][-1] < 10.0
        assert np.all(np.diff(s["due"]) > 0)
        assert s["image"].min() >= 0 and s["image"].max() < mix["pool_images"]
    gaps = np.append(np.diff(a["due"]), 10.0 - a["due"][-1])
    # exponential gaps: mean 1/rate, about 63 % of them below the mean,
    # in no sorted order
    assert abs(gaps.mean() - 0.02) < 1e-9
    assert 0.6 < (gaps < 0.02).mean() < 0.66
    assert not np.all(np.diff(gaps) >= 0)


@pytest.mark.parametrize("other", [7, BIG + 1])
def test_backlog_same_seed_same_order(other):
    kind = registry.module("traffic", "backlog")
    mix = registry.traffic("backlog_224")
    a = kind.schedule(mix, BIG, 10.0)["image"]
    b = kind.schedule(mix, BIG, 10.0)["image"]
    c = kind.schedule(mix, other, 10.0)["image"]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert set(a[:1000]) == set(range(mix["pool_images"]))
    assert kind.warm_lanes(mix, 8) == [8]


def test_image_pool_is_drawn_from_the_seed():
    mix = dict(image_size=16, pool_images=3)
    a = drive.image_pool(mix, 3, BIG)
    assert a.shape == (3, 16, 16, 3) and a.dtype == np.float32
    np.testing.assert_array_equal(a, drive.image_pool(mix, 3, BIG))
    assert not np.array_equal(a, drive.image_pool(mix, 3, BIG + 1))
