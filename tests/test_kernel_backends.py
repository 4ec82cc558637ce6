"""Kernel backend registry: Pallas-vs-XLA parity, registry-driven.

Three layers, mirroring the registry's contract (DESIGN.md §8):

  * primitive level — for *every* registered primitive, the Pallas body
    (interpret mode on this CPU container) is bit-exact with the XLA
    reference body on random inputs (a coverage guard fails the suite if a
    primitive is registered without a parity case here);
  * engine level — every registered engine scores identically under
    ``cfg.backend='xla'`` and ``'pallas_interpret'``, and the jit-native
    ``train_step`` is bit-exact across backends in both learning modes;
  * sharded level (subprocess, forced 4-device host platform) — the
    clause-sharded ``scores`` and ``train_step`` run the Pallas route
    (``pallas_call`` present in the lowered jaxpr) with the single (B, m)
    vote all-reduce still the only scores collective, bit-exact with the
    single-device XLA path in both learning modes.
"""
import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TMConfig, TMState, bundle_scores, init_bundle, registered_engines,
    train_step)
from repro.core.bitpack import pack_bits, packed_literals
from repro.kernels import backend as kbackend

SRC = str(Path(__file__).resolve().parents[1] / "src")

CFG = TMConfig(n_classes=3, n_clauses=16, n_features=12, n_states=50,
               s=3.0, threshold=4)
ALL_EVENTS = CFG.n_classes * CFG.n_clauses * CFG.n_literals


def random_state(cfg, seed=0, density=0.4):
    rng = np.random.default_rng(seed)
    inc = rng.uniform(
        size=(cfg.n_classes, cfg.n_clauses, cfg.n_literals)) < density
    ta = np.where(inc, cfg.n_states + 1, cfg.n_states)
    return TMState(ta_state=jnp.asarray(ta, jnp.int16))


# ---------------------------------------------------------------------------
# Resolution rules
# ---------------------------------------------------------------------------


def test_resolve_backend_auto_is_xla_off_tpu():
    assert jax.default_backend() != "tpu"  # this container
    assert kbackend.resolve_backend("auto") == "xla"
    assert kbackend.pallas_mode() == "pallas_interpret"


def test_resolve_backend_env_override(monkeypatch):
    """The backend is chosen by the platform or by name, never by the
    environment: the old ``REPRO_TM_BACKEND`` hook is gone, and interpret
    mode is reached only through an explicit ``backend=``."""
    monkeypatch.setenv("REPRO_TM_BACKEND", "pallas_interpret")
    assert kbackend.resolve_backend("auto") == "xla"
    assert kbackend.resolve_backend("pallas_interpret") == "pallas_interpret"
    assert kbackend.resolve_backend("xla") == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kbackend.resolve_backend("auto") == "pallas"


def test_resolve_backend_rejects_unknown():
    with pytest.raises(ValueError):
        kbackend.resolve_backend("cuda")
    with pytest.raises(KeyError):
        kbackend.get_primitive("nope")
    with pytest.raises(ValueError):
        TMConfig(n_classes=2, n_clauses=4, n_features=3, backend="nope")


def test_clause_axis_matches_engines():
    from repro.core.engines import CLAUSE_AXIS
    assert kbackend.CLAUSE_AXIS == CLAUSE_AXIS
    for name in kbackend.registered_primitives():
        part = kbackend.get_primitive(name).partitioning
        assert part.in_specs and part.out_spec is not None, name


def test_partitioning_contract_matches_sharded_wiring():
    """The registry's declared ClausePartitioning must equal what the
    sharded layer actually wires (core/distributed.py / core/engines.py) —
    a drifted declaration is a lie in the docs, so pin them together."""
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import STATE_PSPEC
    from repro.core.engines import CLAUSE_AXIS, get_engine

    votes = kbackend.get_primitive("clause_votes").partitioning
    # operands: the bitpack engine's cache spec, replicated literals, the
    # polarity slice make_sharded_scores feeds (P(CLAUSE_AXIS)); result:
    # partial votes completed by the one psum the scores factory emits
    assert votes.in_specs == (get_engine("bitpack").cache_pspec(CFG),
                              P(None, None), P(CLAUSE_AXIS))
    assert votes.out_spec == P(None, None) and votes.vote_reduce

    outputs = kbackend.get_primitive("clause_outputs").partitioning
    assert outputs.in_specs[0] == get_engine("bitpack").cache_pspec(CFG)
    assert outputs.out_spec == P(None, None, CLAUSE_AXIS)
    assert not outputs.vote_reduce

    upd = kbackend.get_primitive("ta_update").partitioning
    # a TA class row (n, 2o) is one class slice of STATE_PSPEC (m, n, 2o)
    assert STATE_PSPEC.ta_state == P(None, CLAUSE_AXIS, None)
    assert upd.in_specs[0] == upd.out_spec == P(CLAUSE_AXIS, None)
    assert not upd.vote_reduce  # feedback is clause-local: no collective

    idx_pspec = get_engine("indexed").cache_pspec(CFG)
    iv = kbackend.get_primitive("indexed_votes").partitioning
    # matmul-form Eq. 4 reads the position matrix with the engine's own
    # cache spec; votes are partial sums under the same single psum as
    # clause_votes, padding rows inert through sign-0 polarity
    assert iv.in_specs == (idx_pspec.pos, P(None, None), P(CLAUSE_AXIS))
    assert iv.out_spec == P(None, None) and iv.vote_reduce
    assert iv.clause_padding == "zero_polarity"

    iu = kbackend.get_primitive("index_update").partitioning
    # batched replay: index operands/results carry the engine cache spec
    # verbatim, event columns replicate (each shard diffs its own slice),
    # and no collective fires — maintenance is clause-local
    assert iu.in_specs[:3] == (idx_pspec.lists, idx_pspec.counts,
                               idx_pspec.pos)
    assert iu.in_specs[3:] == (P(None),) * 5
    assert iu.out_spec == (idx_pspec.lists, idx_pspec.counts, idx_pspec.pos)
    assert not iu.vote_reduce
    assert iu.clause_padding == "masked_active"


# ---------------------------------------------------------------------------
# Primitive-level parity: every registered primitive, Pallas == XLA
# ---------------------------------------------------------------------------


def _primitive_case(name, seed):
    """Random (args, kwargs) for one primitive; extend for new primitives."""
    rng = np.random.default_rng(seed)
    m, n, o, b = 3, 18, 13, 5
    include = rng.uniform(size=(m, n, 2 * o)) < 0.35
    x = jnp.asarray(rng.integers(0, 2, (b, o)), jnp.uint8)
    inc_packed = pack_bits(jnp.asarray(include, jnp.uint8))
    lit_packed = packed_literals(x)
    if name == "clause_votes":
        pol = jnp.asarray(rng.choice([-1, 1], n), jnp.int32)
        return (inc_packed, lit_packed, pol), {}
    if name == "clause_outputs":
        return (inc_packed, lit_packed), {}
    if name == "ta_update":
        L = 2 * o
        return (
            jnp.asarray(rng.integers(1, 101, (n, L)), jnp.int16),
            jnp.asarray(rng.integers(0, 2, L), jnp.uint8),
            jnp.asarray(rng.integers(0, 2, n), jnp.uint8),
            jnp.asarray(rng.integers(0, 2, n), bool),
            jnp.asarray(rng.integers(0, 2, n), bool),
            jnp.asarray(rng.uniform(size=(n, L)), jnp.float32),
        ), {"n_states": 50, "s": 3.7, "boost_true_positive": bool(seed % 2)}
    if name == "indexed_votes":
        from repro.core.types import literals_from_input
        # votes read membership (pos != NA) only — slot values are free
        pos = jnp.where(jnp.asarray(include), 7, -1).astype(jnp.int32)
        pol = jnp.asarray(rng.choice([-1, 1], n), jnp.int32)
        return (pos, literals_from_input(x), pol), {}
    if name == "index_update":
        from repro.core import indexing
        from repro.core.types import include_mask
        cfg = dataclasses.replace(
            CFG, n_clauses=n, n_features=o, index_capacity=n)
        ta = np.where(include, cfg.n_states + 1, cfg.n_states)
        state = TMState(ta_state=jnp.asarray(ta, jnp.int16))
        idx = indexing.build_index(cfg, state, n)
        inc = np.asarray(include_mask(cfg, state))
        # 12 distinct boundary crossings (direction from the current mask:
        # insert where excluded, delete where included) + an invalid tail
        cells = rng.choice(m * n * 2 * o, size=12, replace=False)
        ci, rem = np.divmod(cells, n * 2 * o)
        cj, ck = np.divmod(rem, 2 * o)
        valid = np.ones(12, bool)
        valid[-3:] = False
        return (idx.lists, idx.counts, idx.pos,
                jnp.asarray(ci, jnp.int32), jnp.asarray(cj, jnp.int32),
                jnp.asarray(ck, jnp.int32),
                jnp.asarray(~inc[ci, cj, ck]), jnp.asarray(valid)), {}
    raise NotImplementedError(
        f"primitive {name!r} registered without a parity case — add one")


@pytest.mark.parametrize("name", kbackend.registered_primitives())
@pytest.mark.parametrize("seed", range(3))
def test_primitive_pallas_matches_xla(name, seed):
    args, kwargs = _primitive_case(name, seed)
    want = kbackend.resolve(name, "xla")(*args, **kwargs)
    got = kbackend.resolve(name, "pallas_interpret")(*args, **kwargs)
    want_leaves = jax.tree_util.tree_leaves(want)
    got_leaves = jax.tree_util.tree_leaves(got)
    assert len(got_leaves) == len(want_leaves)  # e.g. index_update's 3-tuple
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_every_primitive_has_a_case():
    for name in kbackend.registered_primitives():
        _primitive_case(name, 0)  # raises NotImplementedError when missing


# ---------------------------------------------------------------------------
# Engine-level parity: cfg.backend threads through scores and training
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", registered_engines())
def test_engine_scores_parity_across_backends(name):
    state = random_state(CFG, 3)
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.integers(0, 2, (7, CFG.n_features)), jnp.uint8)
    outs = {}
    for backend in ("xla", "pallas_interpret"):
        cfg = dataclasses.replace(CFG, backend=backend)
        bundle = init_bundle(cfg, state=state, engines=(name,))
        outs[backend] = np.asarray(bundle_scores(bundle, xs, engine=name))
    np.testing.assert_array_equal(outs["pallas_interpret"], outs["xla"],
                                  err_msg=name)


def test_bitpack_xla_alias_shares_cache_and_pins_backend():
    from repro.core.engines import get_engine
    a, b = get_engine("bitpack"), get_engine("bitpack_xla")
    assert a.cache_key == b.cache_key == "bitpack"
    assert b.backend == "xla" and a.backend is None
    # the alias ignores a pallas cfg: same class, pinned resolution
    cfg = dataclasses.replace(CFG, backend="pallas_interpret")
    assert b._votes(cfg) is kbackend.resolve("clause_votes", "xla")


@pytest.mark.parametrize("parallel", [False, True])
def test_train_step_parity_across_backends(parallel):
    """The fused Pallas training round (clause outputs → ta_update kernel)
    is bit-exact with the XLA bodies, engine caches included."""
    rng = np.random.default_rng(0)
    bundles = {}
    for backend in ("xla", "pallas_interpret"):
        cfg = dataclasses.replace(CFG, backend=backend)
        bundle = init_bundle(cfg, state=random_state(cfg, 1))
        key = jax.random.key(2)
        data = np.random.default_rng(7)
        for _ in range(3):
            key, sub = jax.random.split(key)
            xs = jnp.asarray(data.integers(0, 2, (6, cfg.n_features)),
                             jnp.uint8)
            ys = jnp.asarray(data.integers(0, cfg.n_classes, 6), jnp.int32)
            bundle = train_step(bundle, xs, ys, sub, parallel=parallel,
                                max_events=ALL_EVENTS)
        bundles[backend] = bundle
    ref = bundles["xla"]
    got = bundles["pallas_interpret"]
    np.testing.assert_array_equal(np.asarray(got.state.ta_state),
                                  np.asarray(ref.state.ta_state))
    assert int(got.event_overflow) == 0
    xs = jnp.asarray(rng.integers(0, 2, (5, CFG.n_features)), jnp.uint8)
    want = np.asarray(bundle_scores(ref, xs, engine="dense"))
    for name in registered_engines():
        np.testing.assert_array_equal(
            np.asarray(bundle_scores(got, xs, engine=name)), want,
            err_msg=name)


# ---------------------------------------------------------------------------
# Sharded: Pallas route under shard_map on a forced 4-device host platform
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.core import (
        TMConfig, TMSession, TMState, Topology, bundle_scores, init_bundle,
        registered_engines, train_step)
    from repro.launch import hlo as hlo_mod

    cfg = TMConfig(n_classes=3, n_clauses=16, n_features=12, n_states=50,
                   s=3.0, threshold=4)
    ALL = cfg.n_classes * cfg.n_clauses * cfg.n_literals
    rng = np.random.default_rng(0)
    inc = rng.uniform(size=(3, 16, 24)) < 0.4
    state = TMState(ta_state=jnp.asarray(
        np.where(inc, cfg.n_states + 1, cfg.n_states), jnp.int16))
    xs_eval = jnp.asarray(rng.integers(0, 2, (8, 12)), jnp.uint8)

    ref = init_bundle(dataclasses.replace(cfg, backend="xla"), state=state)
    want = np.asarray(bundle_scores(ref, xs_eval, engine="dense"))

    # Topology(backend=...) overrides the config's choice at resolution
    stm = TMSession(cfg, Topology(clause_shards=4,
                                  backend="pallas_interpret"),
                    max_events=ALL)
    assert stm.cfg.backend == "pallas_interpret"
    assert stm.describe()["backend"] == "pallas_interpret"
    sb = stm.prepare(state)

    # ---- sharded scores: every engine bit-exact; bitpack runs the kernel
    for name in registered_engines():
        got = np.asarray(stm.scores(sb, xs_eval, engine=name))
        np.testing.assert_array_equal(got, want, err_msg=name)
    print("backend-sharded-scores-ok")

    # the bitpack route really is Pallas (kernel call in the jaxpr) and the
    # vote all-reduce is still the one and only collective
    from repro.core.distributed import make_sharded_scores
    from repro.core.engines import get_engine
    eng = get_engine("bitpack")
    s = make_sharded_scores(stm.cfg, stm.mesh, engine="bitpack")
    cache = sb.caches[eng.cache_key]
    jaxpr = str(jax.make_jaxpr(s.jitted)(cache, s.pol, xs_eval))
    assert "pallas_call" in jaxpr, "bitpack did not route through Pallas"
    coll = hlo_mod.collective_stats(
        s.jitted.lower(cache, s.pol, xs_eval).compile().as_text())
    assert coll.count == 1 and set(coll.by_kind) == {"all-reduce"}, (
        coll.count, coll.by_kind)
    # the XLA route on the same mesh has no kernel call
    s_x = make_sharded_scores(dataclasses.replace(stm.cfg, backend="xla"),
                              stm.mesh, engine="bitpack")
    assert "pallas_call" not in str(
        jax.make_jaxpr(s_x.jitted)(cache, s_x.pol, xs_eval))
    print("backend-sharded-route-ok")

    # ---- sharded fused training round: both learning modes, bit-exact
    for parallel in (False, True):
        st_sh = TMSession(cfg, Topology(clause_shards=4,
                                        backend="pallas_interpret"),
                          parallel=parallel, max_events=ALL)
        b_ref = init_bundle(dataclasses.replace(cfg, backend="xla"),
                            state=state)
        b_sh = st_sh.prepare(state)
        key = jax.random.key(1)
        data = np.random.default_rng(5)
        for _ in range(2):
            key, sub = jax.random.split(key)
            bx = jnp.asarray(data.integers(0, 2, (8, 12)), jnp.uint8)
            by = jnp.asarray(data.integers(0, 3, 8), jnp.int32)
            b_ref = train_step(b_ref, bx, by, sub, parallel=parallel,
                               max_events=ALL)
            b_sh = st_sh.train_step(b_sh, bx, by, sub)
        np.testing.assert_array_equal(
            np.asarray(b_sh.state.ta_state), np.asarray(b_ref.state.ta_state),
            err_msg=f"parallel={parallel}")
        assert int(b_sh.event_overflow) == 0
        for name in registered_engines():
            np.testing.assert_array_equal(
                np.asarray(st_sh.scores(b_sh, xs_eval, engine=name)),
                np.asarray(bundle_scores(b_ref, xs_eval, engine="dense")),
                err_msg=f"{name} parallel={parallel}")
    print("backend-sharded-train-ok")
""")


@pytest.mark.slow
def test_kernel_backends_sharded_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for marker in ("backend-sharded-scores-ok", "backend-sharded-route-ok",
                   "backend-sharded-train-ok"):
        assert marker in res.stdout, res.stdout + "\n" + res.stderr
