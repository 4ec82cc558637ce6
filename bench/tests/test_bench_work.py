"""``work.py`` against counts made by hand at the M1 and I1 widths."""
from __future__ import annotations

import pytest

from bench import work

M1 = dict(m=10, n=2000, L=1568)   # 784 features
I1 = dict(m=2, n=2000, L=10000)   # 5000 features


def test_indexed_votes_m1_bucket_32():
    ops, nbytes = work.indexed_votes(M1["m"], M1["n"], M1["L"], 32)
    assert ops == 2 * 32 * 31_360_000 == 2_007_040_000
    # pos once (31.36M int32) + 32 rows of f32 literals + 32x10 int32 votes
    assert nbytes == 125_440_000 + 200_704 + 1_280


def test_indexed_votes_i1_one_row():
    ops, nbytes = work.indexed_votes(I1["m"], I1["n"], I1["L"], 1)
    assert ops == 80_000_000
    assert nbytes == 160_000_000 + 40_000 + 8


@pytest.mark.parametrize("shape, expect", [
    (I1, 20_000_000 * 8 + 40_000 + 8_000),
    (M1, 3_136_000 * 8 + 6_272 + 8_000),
])
def test_ta_update_bytes(shape, expect):
    assert work.ta_update(shape["n"], shape["L"]) == (0, expect)


@pytest.mark.parametrize("shape, words", [(I1, 313), (M1, 49)])
def test_clause_outputs_bytes(shape, words):
    n = shape["n"]
    assert work.clause_outputs(n, shape["L"]) == (
        0, 4 * n * words + 4 * words + n)


def test_step_counts():
    assert work.serve_ops(1, M1["m"], M1["n"], M1["L"]) == 62_720_000
    assert work.train_ops_per_sample(I1["n"], I1["L"]) == 80_000_000
    assert work.train_ops_per_sample(M1["n"], M1["L"]) == 12_544_000


def test_least_time_takes_the_larger_bound():
    assert work.least_time_s(2e12, 1e9, 1e12, 1e12) == 2.0
    assert work.least_time_s(1e9, 3e12, 1e12, 1e12) == 3.0
