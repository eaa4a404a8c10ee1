"""The traffic generator repeats for a seed."""

import numpy as np

from bench import harness
from bench.traffic import generator

CFG = harness.load_json(harness.BENCH, "configs", "nmnist-kwn.json")


def test_pool_repeats_for_a_seed_and_differs_across_seeds():
    cfg = dict(CFG, n_in=72, n_steps=4)
    a = generator.pool(cfg, 2 ** 31 + 5, 8)
    b = generator.pool(cfg, 2 ** 31 + 5, 8)
    c = generator.pool(cfg, 2 ** 31 + 6, 8)
    assert a.shape == (8, 4, 72)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert set(np.unique(a)) <= {-1.0, 0.0, 1.0}


def test_pool_density_follows_the_configuration():
    ev = generator.pool(CFG, 3, 16)
    assert ev.shape == (16, 20, 2312)
    assert 0.5 * CFG["events"]["rate"] < np.mean(np.abs(ev)) \
        < 2.0 * CFG["events"]["rate"]


def test_pool_order_repeats():
    assert np.array_equal(generator.pool_order(9, 512, 100),
                          generator.pool_order(9, 512, 100))
