"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitpack import pack_bits, packed_literals, unpack_bits
from repro.core.types import TMConfig, TMState, include_mask
from repro.kernels import clause_eval
from repro.kernels import ref as kref
from repro.kernels import ta_update as ta_mod
from repro.kernels.ops import tm_clause_outputs, tm_predict, tm_votes


def make_case(m, n, o, b, seed, density=0.3):
    rng = np.random.default_rng(seed)
    include = rng.uniform(size=(m, n, 2 * o)) < density
    x = rng.integers(0, 2, (b, o)).astype(np.uint8)
    return jnp.asarray(include), jnp.asarray(x)


# ---------------------------------------------------------------------------
# bitpack round-trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 31, 32, 33, 100, 784, 1568])
def test_pack_unpack_roundtrip(k):
    rng = np.random.default_rng(k)
    bits = jnp.asarray(rng.integers(0, 2, (3, k)), jnp.uint8)
    words = pack_bits(bits)
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_bits(words, k)),
                                  np.asarray(bits))


# ---------------------------------------------------------------------------
# fused votes kernel — sweep shapes incl. unaligned everything
# ---------------------------------------------------------------------------

SHAPES = [
    # (m, n, o, b) — deliberately unaligned to tiles
    (2, 4, 5, 3),
    (3, 8, 17, 9),
    (10, 130, 50, 8),     # clause dim > CLAUSE_TILE
    (2, 256, 784 // 4, 4),
    (1, 2, 2049, 2),      # literal words > LANE after packing? (2·2049/32=129)
]


@pytest.mark.parametrize("shape", SHAPES)
def test_clause_votes_packed_matches_ref(shape):
    m, n, o, b = shape
    include, x = make_case(m, n, o, b, seed=hash(shape) % 2**31)
    lit = jnp.concatenate([x, 1 - x], axis=-1)
    want = kref.clause_votes_ref(include, lit)
    pol = jnp.where(jnp.arange(n) < n // 2, 1, -1).astype(jnp.int32)
    got = clause_eval.clause_votes_packed(
        pack_bits(include.astype(jnp.uint8)), packed_literals(x), pol)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_clause_outputs_packed_matches_ref(shape):
    m, n, o, b = shape
    include, x = make_case(m, n, o, b, seed=hash(shape) % 2**31 + 1)
    lit = jnp.concatenate([x, 1 - x], axis=-1)
    want = kref.clause_outputs_ref(include, lit)
    got = clause_eval.clause_outputs_packed(
        pack_bits(include.astype(jnp.uint8)), packed_literals(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_agrees_with_tm_dense_path():
    """End-to-end: kernel votes == core dense scores (paper Eq. 3)."""
    from repro.core import scores
    cfg = TMConfig(n_classes=4, n_clauses=32, n_features=19, n_states=40)
    rng = np.random.default_rng(0)
    ta = rng.integers(1, 2 * cfg.n_states + 1,
                      (cfg.n_classes, cfg.n_clauses, cfg.n_literals))
    state = TMState(ta_state=jnp.asarray(ta, jnp.int16))
    x = jnp.asarray(rng.integers(0, 2, (6, cfg.n_features)), jnp.uint8)
    got = tm_votes(cfg, state, x)
    want = scores(cfg, state, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(tm_predict(cfg, state, x)),
        np.asarray(jnp.argmax(want, -1)))


# ---------------------------------------------------------------------------
# TA-update kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,o", [(4, 5), (8, 17), (130, 70), (16, 200)])
@pytest.mark.parametrize("seed", [0, 1])
def test_ta_update_matches_ref(n, o, seed):
    rng = np.random.default_rng(seed)
    L = 2 * o
    n_states = 50
    ta = jnp.asarray(rng.integers(1, 2 * n_states + 1, (n, L)), jnp.int16)
    lit = jnp.asarray(rng.integers(0, 2, L), jnp.int8)
    cout = jnp.asarray(rng.integers(0, 2, n), jnp.int8)
    t1 = jnp.asarray(rng.integers(0, 2, n), bool)
    act = jnp.asarray(rng.integers(0, 2, n), bool)
    u = jnp.asarray(rng.uniform(size=(n, L)), jnp.float32)
    got = ta_mod.ta_update(ta, lit, cout, t1, act, u,
                           n_states=n_states, s=3.7)
    want = kref.ta_update_ref(ta, lit, cout, t1, act, u,
                              n_states=n_states, s=3.7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ta_update_bounds():
    """States pinned at the boundaries stay in [1, 2N]."""
    n, L, n_states = 8, 256, 10
    ta = jnp.concatenate([
        jnp.full((n, L // 2), 1, jnp.int16),
        jnp.full((n, L // 2), 2 * n_states, jnp.int16)], axis=1)
    lit = jnp.zeros(L, jnp.int8)
    cout = jnp.ones(n, jnp.int8)
    t1 = jnp.ones(n, bool)
    act = jnp.ones(n, bool)
    u = jnp.zeros((n, L), jnp.float32)  # all transitions fire
    out = np.asarray(ta_mod.ta_update(ta, lit, cout, t1, act, u,
                                      n_states=n_states, s=2.0))
    assert out.min() >= 1 and out.max() <= 2 * n_states


# ---------------------------------------------------------------------------
# TPU blockings: lane-dense vote blocks, partial clause / literal tiles
# ---------------------------------------------------------------------------

# (m, n, o, b): m and b not multiples of 8, n past one clause tile and not a
# multiple of it; m=130 takes the vote block past one 128-lane group
BLOCKING_SHAPES = [(10, 300, 40, 13), (3, 129, 9, 1), (130, 4, 6, 3)]


@pytest.mark.parametrize("shape", BLOCKING_SHAPES)
def test_lane_dense_votes_slice_unaligned_shapes(shape):
    m, n, o, b = shape
    include, x = make_case(m, n, o, b, seed=sum(shape))
    lit = jnp.concatenate([x, 1 - x], axis=-1)
    pol = jnp.where(jnp.arange(n) < n // 2, 1, -1).astype(jnp.int32)
    inc_packed = pack_bits(include.astype(jnp.uint8))
    got = clause_eval.clause_votes_packed(inc_packed, packed_literals(x), pol)
    assert got.shape == (b, m)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(kref.clause_votes_ref(include,
                                                                   lit)))
    outs = clause_eval.clause_outputs_packed(inc_packed, packed_literals(x))
    assert outs.shape == (b, m, n) and outs.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(outs), np.asarray(kref.clause_outputs_ref(include, lit)))


@pytest.mark.parametrize("m,n,o,b", [(3, 130, 1100, 11), (10, 9, 20, 13)])
def test_indexed_votes_literal_tiles_match_xla(m, n, o, b):
    """2o = 2200 spans two literal tiles (the second partial): the hit
    counts accumulated over tiles give the XLA body's votes exactly."""
    from repro.kernels import indexed
    from repro.core.types import literals_from_input
    include, x = make_case(m, n, o, b, seed=m * n)
    pos = jnp.where(include, 3, -1).astype(jnp.int32)
    lit = literals_from_input(x)
    pol = jnp.where(jnp.arange(n) % 3 == 0, 1, -1).astype(jnp.int32)
    got = indexed.indexed_votes(pos, lit, pol)
    assert got.shape == (b, m)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(indexed.indexed_votes_xla(pos, lit, pol)))


def test_ta_update_partial_tiles_match_ref():
    """45 clauses (two clause tiles, the second partial) × 2200 literals
    (two literal tiles, the second partial): no padding, same result."""
    rng = np.random.default_rng(3)
    n, L, n_states = 45, 2200, 50
    ta = jnp.asarray(rng.integers(1, 2 * n_states + 1, (n, L)), jnp.int16)
    lit = jnp.asarray(rng.integers(0, 2, L), jnp.uint8)
    cout = jnp.asarray(rng.integers(0, 2, n), jnp.uint8)
    t1 = jnp.asarray(rng.integers(0, 2, n), bool)
    act = jnp.asarray(rng.integers(0, 2, n), bool)
    u = jnp.asarray(rng.uniform(size=(n, L)), jnp.float32)
    got = ta_mod.ta_update(ta, lit, cout, t1, act, u, n_states=n_states,
                           s=3.7, boost_true_positive=True)
    want = kref.ta_update_ref(ta, lit, cout, t1, act, u, n_states=n_states,
                              s=3.7, boost_true_positive=True)
    assert got.shape == (n, L) and got.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
