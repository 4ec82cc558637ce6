"""Every generator repeats exactly for a seed and differs across seeds."""
from __future__ import annotations

import numpy as np
import pytest

from bench.gen import arrivals, data, state

BIG = 2**31 + 7   # seeds run past 32 signed bits
FAMILIES = {
    "binarized_images": {"family": "binarized_images", "active": 0.3,
                         "noise": 0.05},
    "bow_documents": {"family": "bow_documents", "active_frac": 0.01,
                      "signal": 40},
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_pool_repeats_per_seed(family):
    a = data.pool(FAMILIES[family], 3, 500, 64, BIG)
    b = data.pool(FAMILIES[family], 3, 500, 64, BIG)
    c = data.pool(FAMILIES[family], 3, 500, 64, BIG + 1)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    assert not np.array_equal(a[0], c[0])
    x, y, proto = a
    assert x.dtype == np.uint8 and set(np.unique(x)) <= {0, 1}
    assert y.min() >= 0 and y.max() < 3 and proto.shape == (3, 500)


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        data.pool({"family": "nope"}, 2, 10, 4, 0)


def test_arrivals_repeat_and_keep_their_count():
    a = arrivals.poisson_fixed_count(1000.0, 2.0, data.rng_for(BIG, 5))
    b = arrivals.poisson_fixed_count(1000.0, 2.0, data.rng_for(BIG, 5))
    c = arrivals.poisson_fixed_count(1000.0, 2.0, data.rng_for(BIG + 1, 5))
    np.testing.assert_array_equal(a, b)
    assert a.size == c.size == 2000 and not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 2.0


def test_state_repeats_per_seed_and_keeps_the_depth_rules():
    tm = {"n_clauses": 8, "n_states": 127}
    _, _, proto = data.pool(FAMILIES["binarized_images"], 3, 40, 4, BIG)
    a = np.asarray(state.make_state(tm, proto, 6, BIG))
    b = np.asarray(state.make_state(tm, proto, 6, BIG))
    c = np.asarray(state.make_state(tm, proto, 6, BIG + 1))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.shape == (3, 8, 80) and a.dtype == np.int16
    assert a.min() >= 1 and a.max() <= 254
    lit = np.concatenate([proto, 1 - proto], axis=-1)
    # no clause includes a literal its prototype class has false; positive
    # clauses of class i are drawn from class i's prototype
    include = a > 127
    assert not np.any(include[:, :4] & (lit[:, None, :] == 0))
