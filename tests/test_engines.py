"""Engine registry: parity across all registered engines, cache sync.

Driven through ``registered_engines()`` so any newly registered engine is
covered automatically — the paper's core claim (same predictions, less work)
becomes a standing invariant of the registry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TMConfig, TMState, bundle_scores, get_engine, init_bundle,
    registered_engines, train_step_jit, validate,
)
from repro.core.engines import cache_provider
from repro.core.indexing import events_from_transition
from repro.core.types import include_mask

CFG = TMConfig(n_classes=3, n_clauses=8, n_features=6, n_states=50,
               s=3.0, threshold=4, empty_clause_output=1)
ALL_EVENTS = CFG.n_classes * CFG.n_clauses * CFG.n_literals


def random_state(cfg, seed=0, density=0.4):
    rng = np.random.default_rng(seed)
    inc = rng.uniform(
        size=(cfg.n_classes, cfg.n_clauses, cfg.n_literals)) < density
    ta = np.where(inc, cfg.n_states + 1, cfg.n_states)
    return TMState(ta_state=jnp.asarray(ta, jnp.int16))


def random_inputs(cfg, seed, batch=7):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2, (batch, cfg.n_features)), jnp.uint8)


# ---------------------------------------------------------------------------
# Parity: every registered engine ≡ dense (paper Eq. 4 mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", registered_engines())
@pytest.mark.parametrize("seed", range(3))
def test_engine_scores_equal_dense(name, seed):
    state = random_state(CFG, seed)
    xs = random_inputs(CFG, 100 + seed)
    eng = get_engine(name)
    cache = eng.prepare(CFG, state)
    got = eng.scores(CFG, cache, xs)
    want = get_engine("dense").scores(CFG, state, xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", registered_engines())
def test_engine_argmax_matches_dense(name):
    state = random_state(CFG, 7, density=0.25)
    xs = random_inputs(CFG, 77, batch=9)
    eng = get_engine(name)
    got = jnp.argmax(eng.scores(CFG, eng.prepare(CFG, state), xs), axis=-1)
    want = jnp.argmax(get_engine("dense").scores(CFG, state, xs), axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_get_engine_unknown_name():
    with pytest.raises(KeyError):
        get_engine("nope")


# ---------------------------------------------------------------------------
# Parity survives a *jitted* training run with cache maintenance enabled
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("parallel", [False, True])
def test_engine_parity_after_jitted_training(parallel):
    bundle = init_bundle(CFG)
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    for step in range(3):
        xs = jnp.asarray(rng.integers(0, 2, (12, CFG.n_features)), jnp.uint8)
        ys = jnp.asarray(rng.integers(0, CFG.n_classes, 12), jnp.int32)
        key, sub = jax.random.split(key)
        bundle = train_step_jit(bundle, xs, ys, sub, parallel=parallel,
                                max_events=ALL_EVENTS)
    # the paper's index is still a valid mirror of the state
    for name, ok in validate(CFG, bundle.state, bundle.index).items():
        assert bool(ok), name
    xs = random_inputs(CFG, 999, batch=11)
    want = bundle_scores(bundle, xs, engine="dense")
    for name in registered_engines():
        got = bundle_scores(bundle, xs, engine=name)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=name)


# ---------------------------------------------------------------------------
# Incremental cache maintenance ≡ rebuild, per provider
# ---------------------------------------------------------------------------

def _transition_events(seed, max_events=ALL_EVENTS):
    s0 = random_state(CFG, seed)
    s1 = random_state(CFG, 50 + seed)
    buf = events_from_transition(include_mask(CFG, s0),
                                 include_mask(CFG, s1), max_events)
    return s0, s1, buf


@pytest.mark.parametrize("max_events", [ALL_EVENTS, 1],
                         ids=["every_event", "overflowing"])
@pytest.mark.parametrize("seed", range(3))
def test_bitpack_update_cache_is_fresh_pack(seed, max_events):
    """The packed words after a step are a fresh pack of the new state,
    also when the event buffer drops crossings."""
    s0, s1, buf = _transition_events(seed, max_events)
    assert (int(buf.overflow) > 0) == (max_events == 1)
    prov = cache_provider("bitpack")
    got = prov.update_cache(CFG, prov.prepare(CFG, s0), s1, buf.events)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(prov.prepare(CFG, s1)))


@pytest.mark.parametrize("key", ["dense", "bitpack", "compact", "indexed"])
def test_update_cache_matches_prepare_scores(key):
    """Provider-level contract: update_cache(prepare(s0), events) scores
    identically to prepare(s1), for every distinct cache slot."""
    s0, s1, buf = _transition_events(11)
    prov = cache_provider(key)
    synced = prov.update_cache(CFG, prov.prepare(CFG, s0), s1, buf.events)
    xs = random_inputs(CFG, 1234, batch=5)
    eng = get_engine(key)  # cache_key == a registered engine name here
    np.testing.assert_array_equal(
        np.asarray(eng.scores(CFG, synced, xs)),
        np.asarray(eng.scores(CFG, prov.prepare(CFG, s1), xs)))
