"""The benchmark's RF generator is a function of the seed alone."""

import json
import os

import numpy as np
import pytest

from bench import rf

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "small-mixed.json")


@pytest.fixture(scope="module")
def conf():
    with open(FIXTURE) as f:
        return json.load(f)


def test_same_seed_same_rf_on_any_batch(conf):
    big = 2 ** 40 + 3
    a = rf.make_pool(conf["pipeline"], conf["rf"], [big, 7, 2 ** 64 - 1])
    b = rf.make_pool(conf["pipeline"], conf["rf"], [big])
    c = rf.make_pool(conf["pipeline"], conf["rf"], [7, 2 ** 64 - 1, big])
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[0], c[2])
    np.testing.assert_array_equal(a[1], c[0])


def test_rf_shape_scaling_and_seed_dependence(conf):
    g = conf["pipeline"]
    x = rf.make_pool(g, conf["rf"], [1, 2])
    assert x.shape == (2, g["n_l"], g["n_c"], g["n_f"])
    assert x.dtype == np.int16
    assert np.abs(x[0]).max() in (29999, 30000)
    assert not np.array_equal(x[0], x[1])
    # moving scatterers: the frames differ from one another
    assert not np.array_equal(x[0, ..., 0], x[0, ..., -1])


@pytest.mark.parametrize("seed", [-1, 2 ** 64])
def test_seed_outside_64_bits_is_refused(seed):
    with pytest.raises(ValueError):
        rf.key_data(seed)
