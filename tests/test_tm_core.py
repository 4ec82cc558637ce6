"""TM forward/learning semantics vs the pure-numpy oracle (paper §2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TMConfig, TMState, clause_votes, dense_clause_outputs, init_tm, predict,
    scores, update_batch_parallel, update_batch_sequential, update_sample,
)
from repro.core import ref
from repro.core import tm as tm_mod
from repro.core.types import literals_from_input

CFG = TMConfig(n_classes=3, n_clauses=8, n_features=6, n_states=50,
               s=3.0, threshold=4)


def random_state(cfg, seed=0):
    rng = np.random.default_rng(seed)
    ta = rng.integers(1, 2 * cfg.n_states + 1,
                      (cfg.n_classes, cfg.n_clauses, cfg.n_literals))
    return TMState(ta_state=jnp.asarray(ta, jnp.int16))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("empty_output", [0, 1])
def test_dense_clause_outputs_match_ref(seed, empty_output):
    state = random_state(CFG, seed)
    rng = np.random.default_rng(100 + seed)
    xs = rng.integers(0, 2, (5, CFG.n_features)).astype(np.uint8)
    got = dense_clause_outputs(CFG, state, jnp.asarray(xs),
                               empty_output=empty_output)
    for b in range(xs.shape[0]):
        want = ref.clause_outputs_ref(np.asarray(state.ta_state), xs[b],
                                      CFG.n_states, empty_output)
        np.testing.assert_array_equal(np.asarray(got[b]), want)


def test_votes_match_ref():
    state = random_state(CFG, 7)
    rng = np.random.default_rng(7)
    xs = rng.integers(0, 2, (4, CFG.n_features)).astype(np.uint8)
    out = dense_clause_outputs(CFG, state, jnp.asarray(xs))
    votes = clause_votes(CFG, out)
    for b in range(4):
        want = ref.votes_ref(np.asarray(out[b]))
        np.testing.assert_array_equal(np.asarray(votes[b]), want)


@pytest.mark.parametrize("positive_round", [True, False])
@pytest.mark.parametrize("seed", range(3))
def test_class_round_matches_ref(positive_round, seed):
    """Feedback with injected uniforms is bit-exact vs the numpy oracle."""
    state = random_state(CFG, seed)
    rng = np.random.default_rng(200 + seed)
    x = rng.integers(0, 2, CFG.n_features).astype(np.uint8)
    lit = np.concatenate([x, 1 - x]).astype(np.uint8)
    gate_u = rng.uniform(size=CFG.n_clauses)
    t1_u = rng.uniform(size=(CFG.n_clauses, CFG.n_literals))
    rands = tm_mod.FeedbackRands(clause_gate=jnp.asarray(gate_u),
                                 type_i=jnp.asarray(t1_u))
    got = tm_mod._class_round(CFG, state.ta_state[1], jnp.asarray(lit),
                              rands, jnp.asarray(positive_round))
    want = ref.class_round_ref(
        np.asarray(state.ta_state[1]), lit, gate_u, t1_u,
        n_states=CFG.n_states, s=CFG.s, threshold=CFG.threshold,
        half=CFG.n_clauses // 2, positive_round=positive_round)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_update_sample_touches_two_classes():
    state = init_tm(CFG)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 2, CFG.n_features),
                    jnp.uint8)
    new = update_sample(CFG, state, x, jnp.asarray(1), jax.random.key(0))
    changed = np.asarray(
        (new.ta_state != state.ta_state).any(axis=(1, 2)))
    assert changed[1]                    # target class updated
    assert changed.sum() <= 2            # at most one negative class


def test_states_stay_in_bounds_and_learning_learns():
    """A separable toy problem: class = x_0. TM should fit it quickly."""
    cfg = TMConfig(n_classes=2, n_clauses=10, n_features=4, n_states=50,
                   s=3.0, threshold=5)
    rng = np.random.default_rng(3)
    xs = rng.integers(0, 2, (256, cfg.n_features)).astype(np.uint8)
    ys = xs[:, 0].astype(np.int32)
    state = init_tm(cfg)
    key = jax.random.key(42)
    fit = jax.jit(lambda s, x, y, k: update_batch_sequential(cfg, s, x, y, k))
    for ep in range(3):
        key, sub = jax.random.split(key)
        state = fit(state, jnp.asarray(xs), jnp.asarray(ys), sub)
    ta = np.asarray(state.ta_state)
    assert ta.min() >= 1 and ta.max() <= 2 * cfg.n_states
    acc = float(tm_mod.accuracy(cfg, state, jnp.asarray(xs), jnp.asarray(ys)))
    assert acc > 0.95, f"TM failed to learn separable toy problem: acc={acc}"


def test_batch_parallel_update_changes_state_and_stays_bounded():
    cfg = CFG
    state = random_state(cfg, 11)
    rng = np.random.default_rng(11)
    xs = jnp.asarray(rng.integers(0, 2, (16, cfg.n_features)), jnp.uint8)
    ys = jnp.asarray(rng.integers(0, cfg.n_classes, 16), jnp.int32)
    new = update_batch_parallel(cfg, state, xs, ys, jax.random.key(5))
    ta = np.asarray(new.ta_state)
    assert ta.min() >= 1 and ta.max() <= 2 * cfg.n_states
    assert (ta != np.asarray(state.ta_state)).any()


def test_predict_shape_and_range():
    state = random_state(CFG, 2)
    xs = jnp.asarray(np.random.default_rng(1).integers(0, 2, (9, CFG.n_features)),
                     jnp.uint8)
    p = predict(CFG, state, xs)
    assert p.shape == (9,)
    assert int(p.min()) >= 0 and int(p.max()) < CFG.n_classes


# ---------------------------------------------------------------------------
# Per-shard draws: a clause shard's rows of the full uniform draw
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,width,a,b", [
    (37, 130, 5, 20), (40, 1, 0, 40), (100, 333, 97, 100),
    (64, 200, 0, 16), (9, 2 * 7, 3, 9)])
def test_uniform_rows_equal_rows_of_the_full_draw(n, width, a, b):
    key = jax.random.key(n * 1000 + width)
    full = jax.random.uniform(key, (n, width))
    part = tm_mod.uniform_rows(key, jnp.int32(a), b - a, width)
    assert part.dtype == jnp.float32 and part.shape == (b - a, width)
    np.testing.assert_array_equal(np.asarray(part), np.asarray(full[a:b]))


@pytest.mark.parametrize("a,b", [(0, 8), (2, 6), (5, 8)])
def test_feedback_rands_of_a_row_block_equal_the_full_draw(a, b):
    cfg = TMConfig(n_classes=2, n_clauses=8, n_features=67, n_states=50,
                   s=3.0, threshold=4)          # 2o = 134: not a lane multiple
    key = jax.random.key(7)
    full = tm_mod.draw_feedback_rands(cfg, key)
    rows = tm_mod.draw_feedback_rands(cfg, key, jnp.int32(a), b - a)
    np.testing.assert_array_equal(np.asarray(rows.clause_gate),
                                  np.asarray(full.clause_gate[a:b]))
    np.testing.assert_array_equal(np.asarray(rows.type_i),
                                  np.asarray(full.type_i[a:b]))


@pytest.mark.parametrize("start,width", [
    (0, 40_000), (107_374, 40_000), (107_375, 40_000), (2**20 + 3, 2**13 + 5),
    (2**31 - 2, 65_537), (3_000_000_000, 65_537), (4_000_000_000, 7)])
def test_flat_counters_carry_the_high_word(start, width):
    n_rows = 3
    hi, lo = tm_mod.flat_counters(jnp.uint32(start), n_rows, width)
    rows = np.arange(start, start + n_rows, dtype=np.uint64)
    want = (rows[:, None] * np.uint64(width)
            + np.arange(width, dtype=np.uint64))
    np.testing.assert_array_equal(np.asarray(hi),
                                  (want >> np.uint64(32)).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(lo),
                                  (want & np.uint64(2**32 - 1))
                                  .astype(np.uint32))


def test_flat_counters_carry_across_a_row_end():
    # the last columns of a row cross 2**32 inside the row
    width = 1 << 20
    start = (1 << 32) // width - 1                  # row ends exactly at 2**32
    hi, lo = tm_mod.flat_counters(jnp.uint32(start), 2, width)
    want = (np.arange(start, start + 2, dtype=np.uint64)[:, None]
            * np.uint64(width) + np.arange(width, dtype=np.uint64))
    np.testing.assert_array_equal(np.asarray(hi),
                                  (want >> np.uint64(32)).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(lo),
                                  (want & np.uint64(2**32 - 1)).astype(np.uint32))
