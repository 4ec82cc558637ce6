"""Clause index (paper §3): O(1) maintenance, inference equivalence."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property test uses hypothesis when present; seeded fallback otherwise
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import (
    TMConfig, TMState, apply_events, build_index, compact,
    compact_apply_events, compact_eval, compact_scores, delete,
    dense_clause_outputs, empty_index, events_from_transition,
    index_update, indexed_scores, indexed_work, insert, init_tm, scores,
    validate,
)
from repro.core import ref
from repro.core.indexing import Event
from repro.core.types import include_mask

CFG = TMConfig(n_classes=3, n_clauses=8, n_features=6, n_states=50,
               s=3.0, threshold=4, empty_clause_output=1)
CAP = CFG.n_clauses  # worst-case capacity


def random_state(cfg, seed=0, density=0.4):
    rng = np.random.default_rng(seed)
    inc = rng.uniform(size=(cfg.n_classes, cfg.n_clauses, cfg.n_literals)) < density
    ta = np.where(inc, cfg.n_states + 1, cfg.n_states)
    return TMState(ta_state=jnp.asarray(ta, jnp.int16))


# ---------------------------------------------------------------------------
# Structure invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_build_index_invariants(seed):
    state = random_state(CFG, seed)
    idx = build_index(CFG, state, CAP)
    checks = validate(CFG, state, idx)
    for name, ok in checks.items():
        assert bool(ok), name


def test_empty_index_is_valid():
    state = init_tm(CFG)
    idx = empty_index(CFG, CAP)
    checks = validate(CFG, state, idx)
    for name, ok in checks.items():
        assert bool(ok), name


def test_insert_then_delete_roundtrip():
    """Paper's step-by-step example semantics: swap-with-last + pos fixup."""
    idx = empty_index(CFG, CAP)
    i, k = jnp.asarray(1), jnp.asarray(3)
    # insert clauses 2, 5, 7 into list (1, 3)
    for j in (2, 5, 7):
        idx = insert(idx, i, jnp.asarray(j), k)
    assert int(idx.counts[1, 3]) == 3
    np.testing.assert_array_equal(np.asarray(idx.lists[1, 3, :3]), [2, 5, 7])
    assert int(idx.pos[1, 5, 3]) == 1
    # delete the middle element: 7 swaps into its slot
    idx = delete(idx, i, jnp.asarray(5), k)
    assert int(idx.counts[1, 3]) == 2
    np.testing.assert_array_equal(np.asarray(idx.lists[1, 3, :2]), [2, 7])
    assert int(idx.pos[1, 7, 3]) == 1
    assert int(idx.pos[1, 5, 3]) == -1


def _check_event_replay_equals_rebuild(ops):
    """Property body: replaying any insert/delete sequence ≡ batch rebuild."""
    inc = np.zeros((CFG.n_classes, CFG.n_clauses, CFG.n_literals), bool)
    idx = empty_index(CFG, CAP)
    for (i, j, k) in ops:
        if inc[i, j, k]:
            idx = delete(idx, jnp.asarray(i), jnp.asarray(j), jnp.asarray(k))
            inc[i, j, k] = False
        else:
            idx = insert(idx, jnp.asarray(i), jnp.asarray(j), jnp.asarray(k))
            inc[i, j, k] = True
    ta = np.where(inc, CFG.n_states + 1, CFG.n_states)
    state = TMState(ta_state=jnp.asarray(ta, jnp.int16))
    checks = validate(CFG, state, idx)
    for name, ok in checks.items():
        assert bool(ok), name
    # counts must agree with a fresh build (list *order* may differ — the
    # index is a set structure; validate() checks the bijection)
    fresh = build_index(CFG, state, CAP)
    np.testing.assert_array_equal(np.asarray(idx.counts), np.asarray(fresh.counts))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, CFG.n_classes - 1),
                              st.integers(0, CFG.n_clauses - 1),
                              st.integers(0, CFG.n_literals - 1)),
                    min_size=1, max_size=40))
    def test_event_replay_equals_rebuild(ops):
        _check_event_replay_equals_rebuild(ops)
else:
    @pytest.mark.parametrize("seed", range(6))
    def test_event_replay_equals_rebuild(seed):
        rng = np.random.default_rng(seed)
        n_ops = int(rng.integers(1, 41))
        ops = [(int(rng.integers(0, CFG.n_classes)),
                int(rng.integers(0, CFG.n_clauses)),
                int(rng.integers(0, CFG.n_literals)))
               for _ in range(n_ops)]
        _check_event_replay_equals_rebuild(ops)


def test_apply_events_masked_buffer():
    state0 = init_tm(CFG)
    state1 = random_state(CFG, 5)
    old_inc = include_mask(CFG, state0)
    new_inc = include_mask(CFG, state1)
    n_changed = int(np.asarray(old_inc != new_inc).sum())
    buf = events_from_transition(old_inc, new_inc, max_events=n_changed + 8)
    assert int(buf.overflow) == 0
    idx = apply_events(empty_index(CFG, CAP), buf.events)
    checks = validate(CFG, state1, idx)
    for name, ok in checks.items():
        assert bool(ok), name


# ---------------------------------------------------------------------------
# Inference equivalence (the paper's core claim: same predictions, less work)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_indexed_scores_equal_dense_scores(seed):
    state = random_state(CFG, seed)
    idx = build_index(CFG, state, CAP)
    rng = np.random.default_rng(300 + seed)
    xs = jnp.asarray(rng.integers(0, 2, (7, CFG.n_features)), jnp.uint8)
    got = indexed_scores(CFG, idx, xs)
    want = scores(CFG, state, xs)  # empty_clause_output=1 (paper Eq. 4 mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", range(3))
def test_indexed_scores_match_numpy_list_oracle(seed):
    state = random_state(CFG, seed)
    idx = build_index(CFG, state, CAP)
    rng = np.random.default_rng(400 + seed)
    xs = rng.integers(0, 2, (5, CFG.n_features)).astype(np.uint8)
    got = np.asarray(indexed_scores(CFG, idx, jnp.asarray(xs)))
    for b in range(xs.shape[0]):
        want = ref.indexed_scores_ref(np.asarray(idx.lists),
                                      np.asarray(idx.counts),
                                      xs[b], CFG.n_clauses)
        np.testing.assert_array_equal(got[b], want)


@pytest.mark.parametrize("seed", range(3))
def test_compact_eval_equals_dense(seed):
    state = random_state(CFG, seed)
    lmax = int(np.asarray(include_mask(CFG, state).sum(-1)).max())
    comp = compact(CFG, state, lmax)
    rng = np.random.default_rng(500 + seed)
    xs = jnp.asarray(rng.integers(0, 2, (6, CFG.n_features)), jnp.uint8)
    got = compact_eval(CFG, comp, xs)
    want = dense_clause_outputs(CFG, state, xs, empty_output=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(compact_scores(CFG, comp, xs)),
        np.asarray(scores(CFG, state, xs)))


@pytest.mark.parametrize("seed", range(3))
def test_compact_apply_events_equals_rebuild(seed):
    """Event replay on the clause-compact layout ≡ fresh compact() build.

    Rows are sets (compact_eval is order-blind), so equality is on lengths
    and per-row membership, not slot order."""
    state0 = random_state(CFG, seed)
    state1 = random_state(CFG, 100 + seed)
    old_inc = include_mask(CFG, state0)
    new_inc = include_mask(CFG, state1)
    l_max = CFG.n_literals  # worst-case capacity
    comp = compact(CFG, state0, l_max)
    n_changed = int(np.asarray(old_inc != new_inc).sum())
    buf = events_from_transition(old_inc, new_inc, n_changed + 4)
    got = compact_apply_events(comp, buf.events)
    want = compact(CFG, state1, l_max)
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(want.lengths))
    got_rows = np.sort(np.asarray(got.lit_idx), axis=-1)
    want_rows = np.sort(np.asarray(want.lit_idx), axis=-1)
    np.testing.assert_array_equal(got_rows, want_rows)


def test_compact_apply_events_overflow_is_contained():
    """Capacity overflow loses literals (config error) but never corrupts
    surviving entries: inserts past ℓ_max clamp, deletes of never-absorbed
    literals are no-ops, and validate_compact flags the loss."""
    from repro.core import validate_compact
    from repro.core.indexing import Event
    l_max = 2
    state0 = TMState(ta_state=jnp.full(
        (CFG.n_classes, CFG.n_clauses, CFG.n_literals), CFG.n_states,
        jnp.int16))
    comp = compact(CFG, state0, l_max)
    # insert 3 literals into clause (0, 0): third overflows
    ev = Event(cls=jnp.zeros(3, jnp.int32),
               clause=jnp.zeros(3, jnp.int32),
               literal=jnp.arange(3, dtype=jnp.int32),
               is_insert=jnp.ones(3, bool), valid=jnp.ones(3, bool))
    comp = compact_apply_events(comp, ev)
    assert int(comp.lengths[0, 0]) == l_max  # clamped, not 3
    # deleting the dropped literal 2 must not disturb survivors {0, 1}
    ev_del = Event(cls=jnp.zeros(1, jnp.int32), clause=jnp.zeros(1, jnp.int32),
                   literal=jnp.full(1, 2, jnp.int32),
                   is_insert=jnp.zeros(1, bool), valid=jnp.ones(1, bool))
    comp = compact_apply_events(comp, ev_del)
    np.testing.assert_array_equal(
        np.sort(np.asarray(comp.lit_idx[0, 0])), [0, 1])
    # validate_compact surfaces the loss vs the true include mask
    ta = np.full((CFG.n_classes, CFG.n_clauses, CFG.n_literals),
                 CFG.n_states, np.int16)
    ta[0, 0, :3] = CFG.n_states + 1  # literals 0,1,2 included, 2 after delete
    ta[0, 0, 2] = CFG.n_states      # literal 2 deleted again
    checks = validate_compact(
        CFG, TMState(ta_state=jnp.asarray(ta)), comp)
    assert bool(checks["overflow_ok"]) and bool(checks["member_ok"])


def test_validate_compact_on_fresh_build():
    from repro.core import validate_compact
    state = random_state(CFG, 3)
    comp = compact(CFG, state, CFG.n_literals)
    for name, ok in validate_compact(CFG, state, comp).items():
        assert bool(ok), name


def test_indexed_work_metric():
    """Work == Σ_{k false} counts[i,k] — the quantity in §3 'Remarks'."""
    state = random_state(CFG, 9, density=0.2)
    idx = build_index(CFG, state, CAP)
    x = np.zeros(CFG.n_features, np.uint8)  # all features 0 → x-literals false
    w = int(indexed_work(idx, jnp.asarray(x[None]))[0])
    counts = np.asarray(idx.counts)
    want = counts[:, :CFG.n_features].sum()  # false literals = first o
    assert w == want


# ---------------------------------------------------------------------------
# Batched replay (index_update) ≡ sequential oracle ≡ fresh build
# ---------------------------------------------------------------------------


def _assert_index_set_equal(got, want):
    """Set-level index equality: counts and membership bit-exact, each list's
    live prefix equal as a *set* (intra-list slot order is the one thing
    sequential swap-with-last and batched compaction may disagree on, and
    nothing observes it), NA padding beyond counts."""
    cnts = np.asarray(want.counts)
    np.testing.assert_array_equal(np.asarray(got.counts), cnts)
    np.testing.assert_array_equal(np.asarray(got.pos) != -1,
                                  np.asarray(want.pos) != -1)
    gl, wl = np.asarray(got.lists), np.asarray(want.lists)
    m, L, cap = gl.shape
    for i in range(m):
        for k in range(L):
            c = cnts[i, k]
            assert sorted(gl[i, k, :c]) == sorted(wl[i, k, :c]), (i, k)
            assert (gl[i, k, c:] == -1).all(), (i, k)


@pytest.mark.parametrize("seed", range(4))
def test_index_update_equals_sequential_and_rebuild(seed):
    """Real transition buffers (masked tails included): batched replay ≡
    scan-of-cond replay ≡ fresh build, and the result validates."""
    state0 = random_state(CFG, seed)
    state1 = random_state(CFG, 50 + seed)
    old_inc = include_mask(CFG, state0)
    new_inc = include_mask(CFG, state1)
    n_changed = int(np.asarray(old_inc != new_inc).sum())
    buf = events_from_transition(old_inc, new_inc, max_events=n_changed + 7)
    idx0 = build_index(CFG, state0, CAP)
    seq = apply_events(idx0, buf.events)
    bat = index_update(idx0, buf.events)
    _assert_index_set_equal(bat, seq)
    _assert_index_set_equal(bat, build_index(CFG, state1, CAP))
    for name, ok in validate(CFG, state1, bat).items():
        assert bool(ok), name


@pytest.mark.parametrize("seed", range(4))
def test_index_update_same_cell_and_same_list_multiples(seed):
    """Adversarial buffers: repeated events on the same (i, j, k) cell
    (strictly alternating — the apply_events precondition), many events on
    the same list, plus a garbage invalid tail that must be ignored."""
    rng = np.random.default_rng(seed)
    state0 = random_state(CFG, seed)
    cur = np.asarray(include_mask(CFG, state0)).copy()
    idx0 = build_index(CFG, state0, CAP)
    # concentrate on two literals so lists absorb many events each, and
    # revisit cells freely: each revisit flips direction (delete-then-insert
    # and insert-then-delete of the same cell both occur)
    ks = rng.choice(CFG.n_literals, size=2, replace=False)
    rows = []
    for _ in range(28):
        i = int(rng.integers(CFG.n_classes))
        j = int(rng.integers(CFG.n_clauses))
        k = int(ks[rng.integers(2)])
        rows.append((i, j, k, not cur[i, j, k], True))
        cur[i, j, k] = not cur[i, j, k]
    for _ in range(4):  # invalid tail: arbitrary fields, must be no-ops
        rows.append((int(rng.integers(CFG.n_classes)),
                     int(rng.integers(CFG.n_clauses)),
                     int(rng.integers(CFG.n_literals)),
                     bool(rng.integers(2)), False))
    ev = Event(
        cls=jnp.asarray([r[0] for r in rows], jnp.int32),
        clause=jnp.asarray([r[1] for r in rows], jnp.int32),
        literal=jnp.asarray([r[2] for r in rows], jnp.int32),
        is_insert=jnp.asarray([r[3] for r in rows]),
        valid=jnp.asarray([r[4] for r in rows]))
    seq = apply_events(idx0, ev)
    bat = index_update(idx0, ev)
    _assert_index_set_equal(bat, seq)
    ta = np.where(cur, CFG.n_states + 1, CFG.n_states)
    state1 = TMState(ta_state=jnp.asarray(ta, jnp.int16))
    _assert_index_set_equal(bat, build_index(CFG, state1, CAP))
    for name, ok in validate(CFG, state1, bat).items():
        assert bool(ok), name


def test_index_update_overflow_counts_match_sequential():
    """Capacity overflow: counts keep the exact sequential value (±1 per
    valid event — the config error stays observable via validate), and the
    in-capacity prefix matches the sequential survivors."""
    cap = 2
    idx0 = empty_index(CFG, cap)
    ev = Event(cls=jnp.zeros(4, jnp.int32),
               clause=jnp.arange(4, dtype=jnp.int32),
               literal=jnp.full(4, 3, jnp.int32),
               is_insert=jnp.ones(4, bool), valid=jnp.ones(4, bool))
    seq = apply_events(idx0, ev)
    bat = index_update(idx0, ev)
    np.testing.assert_array_equal(np.asarray(bat.counts),
                                  np.asarray(seq.counts))
    assert int(bat.counts[0, 3]) == 4 > cap  # overflow accounted, not hidden
    np.testing.assert_array_equal(np.asarray(bat.pos) != -1,
                                  np.asarray(seq.pos) != -1)
    np.testing.assert_array_equal(np.asarray(bat.lists[0, 3]),
                                  np.asarray(seq.lists[0, 3]))  # [0, 1]


# ---------------------------------------------------------------------------
# events_from_transition: cumsum selection ≡ the old stable argsort
# ---------------------------------------------------------------------------


def _events_argsort_reference(old_inc, new_inc, max_events):
    """The pre-optimisation selection, verbatim: stable argsort of the
    changed mask, first max_events cells (regression oracle)."""
    flat = (np.asarray(old_inc) != np.asarray(new_inc)).reshape(-1)
    order = np.argsort(~flat, kind="stable")
    sel = order[:max_events]
    m, n, L = np.asarray(old_inc).shape
    cls, rem = np.divmod(sel, n * L)
    clause, literal = np.divmod(rem, L)
    overflow = max(int(flat.sum()) - max_events, 0)
    return (cls, clause, literal, np.asarray(new_inc).reshape(-1)[sel],
            flat[sel], overflow)


CELLS = CFG.n_classes * CFG.n_clauses * CFG.n_literals


@pytest.mark.parametrize("seed,max_events,change", [
    # room to spare: changed cells + unchanged fill
    pytest.param(0, 64, "random", id="0-64"),
    # tight
    pytest.param(1, 16, "random", id="1-16"),
    # overflow: more changed cells than buffer slots
    pytest.param(2, 5, "random", id="2-5"),
    # buffer larger than the cell count (degenerates to all)
    pytest.param(3, 10_000, "random", id="3-10000"),
    # no cell changed: every slot is unchanged fill, none valid
    pytest.param(4, 64, "none", id="none-changed"),
    # every cell changed, buffer of the cell count: every slot valid
    pytest.param(5, CELLS, "all", id="all-changed"),
    # every cell changed, overflowing buffer
    pytest.param(6, 16, "all", id="all-changed-overflow"),
    # buffer exactly the cell count (the benchmark's worst case)
    pytest.param(7, CELLS, "random", id="cells-exact"),
    # buffer of one slot
    pytest.param(8, 1, "random", id="one-slot"),
    pytest.param(9, 1, "none", id="one-slot-none-changed"),
])
def test_events_from_transition_matches_argsort_reference(seed, max_events,
                                                          change):
    state0 = random_state(CFG, seed)
    state1 = random_state(CFG, 70 + seed)
    old_inc = include_mask(CFG, state0)
    new_inc = {"random": include_mask(CFG, state1), "none": old_inc,
               "all": ~old_inc}[change]
    buf = events_from_transition(old_inc, new_inc, max_events)
    cls, clause, literal, is_insert, valid, overflow = \
        _events_argsort_reference(old_inc, new_inc, max_events)
    np.testing.assert_array_equal(np.asarray(buf.events.cls), cls)
    np.testing.assert_array_equal(np.asarray(buf.events.clause), clause)
    np.testing.assert_array_equal(np.asarray(buf.events.literal), literal)
    np.testing.assert_array_equal(np.asarray(buf.events.is_insert), is_insert)
    np.testing.assert_array_equal(np.asarray(buf.events.valid), valid)
    assert int(buf.overflow) == overflow


@pytest.mark.parametrize("max_events", [16, CELLS])
def test_events_from_transition_uses_no_gather(max_events):
    """The buffer is one scatter and elementwise ops: neither the lowered
    program nor the CPU compile of it holds a gather."""
    inc = jax.ShapeDtypeStruct(
        (CFG.n_classes, CFG.n_clauses, CFG.n_literals), jnp.bool_)
    lowered = jax.jit(events_from_transition, static_argnums=2).lower(
        inc, inc, max_events)
    assert not re.search(r"stablehlo\.\w*gather", lowered.as_text())
    assert not re.search(r"\sgather\(", lowered.compile().as_text())


def test_index_sync_through_learning():
    """Dense learning + event-driven index maintenance stay in sync."""
    from repro.core import update_batch_sequential
    cfg = TMConfig(n_classes=2, n_clauses=6, n_features=5, n_states=20,
                   s=3.0, threshold=3)
    state = init_tm(cfg)
    idx = empty_index(cfg, cfg.n_clauses)
    key = jax.random.key(0)
    rng = np.random.default_rng(0)
    for step in range(5):
        key, sub = jax.random.split(key)
        xs = jnp.asarray(rng.integers(0, 2, (8, cfg.n_features)), jnp.uint8)
        ys = jnp.asarray(rng.integers(0, 2, 8), jnp.int32)
        old_inc = include_mask(cfg, state)
        state = update_batch_sequential(cfg, state, xs, ys, sub)
        new_inc = include_mask(cfg, state)
        buf = events_from_transition(old_inc, new_inc,
                                     max_events=int(cfg.n_classes * cfg.n_clauses * cfg.n_literals))
        assert int(buf.overflow) == 0
        idx = apply_events(idx, buf.events)
        checks = validate(cfg, state, idx)
        for name, ok in checks.items():
            assert bool(ok), f"step {step}: {name}"
