"""TMBundle pytree semantics, TsetlinMachine estimator, session checkpoints."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TMConfig, TMBundle, TsetlinMachine, Topology, bundle_scores, init_bundle,
    registered_engines, train_step, train_step_jit, validate,
)

CFG = TMConfig(n_classes=2, n_clauses=10, n_features=4, n_states=50,
               s=3.0, threshold=5)
ALL_EVENTS = CFG.n_classes * CFG.n_clauses * CFG.n_literals


def toy_data(n=256, seed=3):
    rng = np.random.default_rng(seed)
    xs = rng.integers(0, 2, (n, CFG.n_features)).astype(np.uint8)
    ys = xs[:, 0].astype(np.int32)  # separable: class = x_0
    return jnp.asarray(xs), jnp.asarray(ys)


# ---------------------------------------------------------------------------
# TMBundle pytree
# ---------------------------------------------------------------------------

def test_bundle_is_pytree_with_static_config():
    bundle = init_bundle(CFG)
    leaves, treedef = jax.tree_util.tree_flatten(bundle)
    assert all(isinstance(l, jax.Array) for l in leaves)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.cfg == CFG  # config rides the treedef, not the leaves
    assert set(rebuilt.caches) == set(bundle.caches)


def test_bundle_survives_tree_map():
    bundle = init_bundle(CFG)
    same = jax.tree_util.tree_map(lambda x: x, bundle)
    assert isinstance(same, TMBundle)
    np.testing.assert_array_equal(np.asarray(same.state.ta_state),
                                  np.asarray(bundle.state.ta_state))


def test_engine_subset_bundle():
    bundle = init_bundle(CFG, engines=("dense", "indexed"))
    # dense is cache-less (needs_cache=False): storing the state under a
    # second key would alias buffers inside the donated pytree
    assert set(bundle.caches) == {"indexed"}
    xs, _ = toy_data(8)
    # engines without a maintained cache still score (prepared on the fly)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        got = bundle_scores(bundle, xs, engine="compact")
    want = bundle_scores(bundle, xs, engine="dense")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bundle_scores_warns_once_on_missing_cache_slot():
    """A missing cache slot rebuilds on the fly — with exactly one warning
    per slot, so the per-call rebuild cost can't hide silently."""
    from repro.core import api
    api._REBUILD_WARNED.discard("compact")  # fresh slate for this slot
    bundle = init_bundle(CFG, engines=("indexed",))
    xs, _ = toy_data(6)
    with pytest.warns(RuntimeWarning, match="compact.*rebuilding"):
        bundle_scores(bundle, xs, engine="compact")
    with warnings.catch_warnings():  # second call: silent (warned once)
        warnings.simplefilter("error", RuntimeWarning)
        bundle_scores(bundle, xs, engine="compact")


def test_bundle_scores_reuses_maintained_cache():
    """Regression: a maintained cache must actually be *read*, not silently
    rebuilt from state — probe with a bundle whose cache and state disagree;
    the scores must follow the cache."""
    from repro.core.engines import get_engine
    from repro.core.types import TMState
    rng = np.random.default_rng(0)
    inc = rng.uniform(size=(CFG.n_classes, CFG.n_clauses,
                            CFG.n_literals)) < 0.4
    state_a = TMState(ta_state=jnp.asarray(
        np.where(inc, CFG.n_states + 1, CFG.n_states), jnp.int16))
    cache_a = get_engine("compact").prepare(CFG, state_a)
    blank = init_bundle(CFG, engines=("dense",))  # untrained state
    probe = TMBundle(cfg=CFG, state=blank.state, caches={"compact": cache_a})
    xs, _ = toy_data(8)
    got = np.asarray(bundle_scores(probe, xs, engine="compact"))
    from_cache = np.asarray(
        get_engine("compact").scores(CFG, cache_a, xs))
    from_state = np.asarray(bundle_scores(blank, xs, engine="dense"))
    np.testing.assert_array_equal(got, from_cache)
    assert (got != from_state).any(), \
        "probe degenerate: cache and state scores coincide"


# ---------------------------------------------------------------------------
# train_step purity / jit
# ---------------------------------------------------------------------------

def test_train_step_is_pure_and_jits():
    bundle = init_bundle(CFG)
    xs, ys = toy_data(16)
    before = np.asarray(bundle.state.ta_state).copy()
    # purity via the non-donating eager function (reading the input after a
    # donating jitted call would crash on accelerator backends — by design)
    out_eager = train_step(bundle, xs, ys, jax.random.key(0),
                           max_events=ALL_EVENTS)
    np.testing.assert_array_equal(before, np.asarray(bundle.state.ta_state))
    assert (np.asarray(out_eager.state.ta_state) != before).any()
    # jitted path: advances state and keeps the index valid
    out = train_step_jit(init_bundle(CFG), xs, ys, jax.random.key(0),
                         max_events=ALL_EVENTS)
    assert (np.asarray(out.state.ta_state) != before).any()
    for name, ok in validate(CFG, out.state, out.index).items():
        assert bool(ok), name


def test_train_step_jit_and_eager_agree():
    bundle = init_bundle(CFG)
    xs, ys = toy_data(8, seed=9)
    key = jax.random.key(7)
    eager = train_step(bundle, xs, ys, key, max_events=ALL_EVENTS)
    jitted = train_step_jit(bundle, xs, ys, key, max_events=ALL_EVENTS)
    np.testing.assert_array_equal(np.asarray(eager.state.ta_state),
                                  np.asarray(jitted.state.ta_state))
    np.testing.assert_array_equal(np.asarray(eager.index.counts),
                                  np.asarray(jitted.index.counts))


def test_train_step_mask_ignores_padding_rows():
    """Masked-out rows must not influence the update — padding with zeros or
    with garbage gives bit-identical states; an unmasked garbage row does
    not (the mask is load-bearing)."""
    xs, ys = toy_data(8, seed=4)
    garbage_x = jnp.ones_like(xs[:3])
    garbage_y = jnp.ones_like(ys[:3])
    mask = jnp.arange(11) < 8
    key = jax.random.key(5)
    for parallel in (False, True):
        a = train_step(init_bundle(CFG),
                       jnp.concatenate([xs, jnp.zeros_like(garbage_x)]),
                       jnp.concatenate([ys, jnp.zeros_like(garbage_y)]),
                       key, mask, parallel=parallel, max_events=ALL_EVENTS)
        b = train_step(init_bundle(CFG),
                       jnp.concatenate([xs, garbage_x]),
                       jnp.concatenate([ys, garbage_y]),
                       key, mask, parallel=parallel, max_events=ALL_EVENTS)
        np.testing.assert_array_equal(np.asarray(a.state.ta_state),
                                      np.asarray(b.state.ta_state),
                                      err_msg=f"parallel={parallel}")
        c = train_step(init_bundle(CFG),
                       jnp.concatenate([xs, garbage_x]),
                       jnp.concatenate([ys, garbage_y]),
                       key, jnp.ones(11, bool), parallel=parallel,
                       max_events=ALL_EVENTS)
        assert (np.asarray(c.state.ta_state)
                != np.asarray(a.state.ta_state)).any(), \
            f"parallel={parallel}: garbage rows had no effect unmasked"


def test_bitpack_step_overflows_and_keeps_exact_words():
    """A bitpack-only step with a 1-slot event buffer counts the dropped
    crossings, and its packed words are still a fresh pack of the new
    state."""
    from repro.core.bitpack import pack_bits
    from repro.core.types import include_mask
    xs, ys = toy_data(16)
    out = train_step_jit(init_bundle(CFG, engines=("bitpack",)), xs, ys,
                         jax.random.key(0), max_events=1)
    assert int(out.event_overflow) > 0
    np.testing.assert_array_equal(
        np.asarray(out.caches["bitpack"]),
        np.asarray(pack_bits(include_mask(CFG, out.state))))


# ---------------------------------------------------------------------------
# TsetlinMachine estimator
# ---------------------------------------------------------------------------

def test_estimator_learns_separable_toy():
    xs, ys = toy_data()
    machine = TsetlinMachine(CFG, seed=42).init()
    machine.fit(xs, ys, epochs=3)
    acc = machine.evaluate(xs, ys, engine="indexed")
    assert acc > 0.95, f"estimator failed separable toy: acc={acc}"
    # all engines agree on the trained machine's predictions
    want = np.asarray(machine.predict(xs, engine="dense"))
    for name in registered_engines():
        got = np.asarray(machine.predict(xs, engine=name))
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_estimator_minibatch_fit_and_seeded_reproducibility():
    xs, ys = toy_data(64)
    a = TsetlinMachine(CFG, seed=5).init().fit(xs, ys, epochs=2, batch_size=16)
    b = TsetlinMachine(CFG, seed=5).init().fit(xs, ys, epochs=2, batch_size=16)
    np.testing.assert_array_equal(np.asarray(a.state.ta_state),
                                  np.asarray(b.state.ta_state))


def test_fit_trains_trailing_partial_batch():
    """24 samples at batch_size=16: the trailing 8 pad to the compiled shape
    under a mask — they must train (historically they were dropped), and the
    padded rows must not (zero vs garbage padding is bit-identical)."""
    xs, ys = toy_data(24, seed=8)
    machine = TsetlinMachine(CFG, seed=3).init()
    machine.fit(xs, ys, batch_size=16)

    # reference: the same two steps driven by hand with the same key chain
    ref = TsetlinMachine(CFG, seed=3).init()
    key = ref._next_key(None)
    key, k1 = jax.random.split(key)
    ref.partial_fit(xs[:16], ys[:16], k1, mask=jnp.ones(16, bool))
    key, k2 = jax.random.split(key)
    pad_x = jnp.concatenate([xs[16:], jnp.zeros((8, CFG.n_features),
                                                xs.dtype)])
    pad_y = jnp.concatenate([ys[16:], jnp.zeros((8,), ys.dtype)])
    ref.partial_fit(pad_x, pad_y, k2, mask=jnp.arange(16) < 8)
    np.testing.assert_array_equal(np.asarray(machine.state.ta_state),
                                  np.asarray(ref.state.ta_state))

    # the trailing batch really trained: dropping it changes the state
    dropped = TsetlinMachine(CFG, seed=3).init()
    dkey = dropped._next_key(None)
    dkey, d1 = jax.random.split(dkey)
    dropped.partial_fit(xs[:16], ys[:16], d1, mask=jnp.ones(16, bool))
    assert (np.asarray(machine.state.ta_state)
            != np.asarray(dropped.state.ta_state)).any()


def test_fit_batch_size_larger_than_dataset_raises():
    xs, ys = toy_data(8)
    with pytest.raises(ValueError, match="exceeds dataset size"):
        TsetlinMachine(CFG, seed=0).init().fit(xs, ys, batch_size=16)


def test_estimator_respects_capacity_config():
    cfg = dataclasses.replace(CFG, index_capacity=6, clause_capacity=5)
    bundle = init_bundle(cfg)
    assert bundle.index.capacity == 6
    assert bundle.caches["compact"].lit_idx.shape[-1] == 5


# ---------------------------------------------------------------------------
# Topology + versioned checkpoints (single-device; sharded counterparts in
# tests/test_tm_session.py's forced-multi-device subprocess)
# ---------------------------------------------------------------------------

def test_topology_validates_and_describes():
    t = Topology(clause_shards=2, data_shards=2, engines=["indexed"])
    assert t.engines == ("indexed",)  # normalised to a tuple
    assert t.n_devices == 4 and t.is_sharded
    assert Topology().describe() == {
        "clause_shards": 1, "data_shards": 1, "devices": 1,
        "async_votes": 0}
    with pytest.raises(ValueError, match="must be >= 1"):
        Topology(clause_shards=0)
    with pytest.raises(RuntimeError, match="devices"):
        TsetlinMachine(CFG, topology=Topology(clause_shards=512)).init()


def test_estimator_checkpoint_roundtrip(tmp_path):
    xs, ys = toy_data(32)
    machine = TsetlinMachine(CFG, seed=1).init().fit(xs, ys)
    machine.save(tmp_path / "ck", step=2)
    restored = TsetlinMachine.load(tmp_path / "ck", CFG)
    np.testing.assert_array_equal(
        np.asarray(restored.predict(xs, engine="indexed")),
        np.asarray(machine.predict(xs, engine="indexed")))
    for name, ok in validate(CFG, restored.state, restored.index).items():
        assert bool(ok), name


def test_checkpoint_fingerprint_mismatch_is_clear(tmp_path):
    from repro.checkpoint import CheckpointMismatch
    xs, ys = toy_data(16)
    TsetlinMachine(CFG, seed=1).init().fit(xs, ys).save(tmp_path / "ck")
    # same shapes, different semantics — only the fingerprint can catch it
    other = dataclasses.replace(CFG, s=9.0)
    with pytest.raises(CheckpointMismatch, match="fingerprint mismatch"):
        TsetlinMachine.load(tmp_path / "ck", other)
