"""The TM Pallas kernels compile for a TPU v5e at the paper's widths.

Interpret mode (every other kernel test) cannot see the chip compiler's
rules: block tiling, vector dtypes Mosaic supports, VMEM limits. Here each
kernel is compiled with ``interpret=False`` for one chip of a *described*
``v5e:2x2`` topology — nothing runs, so no TPU is needed — at M1 width
(``configs/tm.py`` ``mnist_like(1)``: 10 classes, 2000 clauses, 1568
literals) and, for ``indexed_votes``, also at I1 width (2o = 10,000).
The learning round's ``clause_outputs_rows`` compiles at M1, I1 and one
I4 clause shard's width (5,000 clauses, 2o = 40,000), and the one-chip I1
train step packs nothing inside its rounds. The clause-sharded train step
compiles for all four chips of the topology at I4 with 20,000 clauses
(``imdb_like(20000, 20000)``), where each chip draws only its own 5,000
clause rows of the uniforms.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, and every
test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.tm import imdb_like, mnist_like
from repro.core import TMConfig, init_bundle, scopes, train_step
from repro.kernels import clause_eval, indexed, ta_update

M1 = mnist_like(1).tm
I1 = imdb_like(5000).tm
I4_SHARD = imdb_like(20000, 5000).tm   # one of four clause shards of I4/20k
SERVE_BATCH = 32


@pytest.fixture(scope="module")
def v5e():
    """The described ``v5e:2x2`` topology (four chips)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _kernel_case(name, cfg):
    """(callable, ((shape, dtype), ...)) for one kernel at ``cfg``'s width;
    the serving kernels at a 32-row batch, the training ones as the learning
    round calls them (one class row, one sample)."""
    m, n, L = cfg.n_classes, cfg.n_clauses, cfg.n_literals
    w = -(-L // 32)
    if name == "clause_votes":
        return (lambda a, b, c: clause_eval.clause_votes_packed(
                    a, b, c, interpret=False),
                (((m, n, w), jnp.uint32), ((SERVE_BATCH, w), jnp.uint32),
                 ((n,), jnp.int32)))
    if name == "clause_outputs":
        return (lambda a, b: clause_eval.clause_outputs_packed(
                    a, b, interpret=False),
                (((1, n, w), jnp.uint32), ((1, w), jnp.uint32)))
    if name == "clause_outputs_rows":
        return (lambda a, b: clause_eval.clause_outputs_rows(
                    a, b, n_states=cfg.n_states, interpret=False),
                (((n, L), jnp.int16), ((L,), jnp.uint8)))
    if name == "indexed_votes":
        return (lambda a, b, c: indexed.indexed_votes(
                    a, b, c, interpret=False),
                (((m, n, L), jnp.int32), ((SERVE_BATCH, L), jnp.uint8),
                 ((n,), jnp.int32)))
    if name == "ta_update":
        return (lambda *a: ta_update.ta_update(
                    *a, n_states=cfg.n_states, s=cfg.s, interpret=False),
                (((n, L), jnp.int16), ((L,), jnp.uint8), ((n,), jnp.int8),
                 ((n,), jnp.bool_), ((n,), jnp.bool_),
                 ((n, L), jnp.float32)))
    raise NotImplementedError(name)


@pytest.mark.parametrize("name,width", [
    ("clause_votes", "M1"),
    ("clause_outputs", "M1"),
    ("indexed_votes", "M1"),
    ("ta_update", "M1"),
    ("indexed_votes", "I1"),
    ("clause_outputs_rows", "M1"),
    ("clause_outputs_rows", "I1"),
    ("clause_outputs_rows", "I4_SHARD"),
])
def test_kernel_compiles_for_v5e(one_chip, name, width):
    cfg = {"M1": M1, "I1": I1, "I4_SHARD": I4_SHARD}[width]
    fn, shapes = _kernel_case(name, cfg)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()



@pytest.fixture(scope="module")
def v5e_train_step_hlo(one_chip):
    """The compiled v5e program of one train step: sequential learning,
    bitpack cache, Pallas rounds, the worst-case event buffer, a small
    width."""
    cfg = TMConfig(n_classes=2, n_clauses=64, n_features=256, n_states=127,
                   s=27.0, threshold=40, backend="pallas")
    batch = 8

    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    bundle = jax.tree.map(place, jax.eval_shape(
        lambda: init_bundle(cfg, engines=("bitpack",),
                            rng=jax.random.key(0))))
    xs = place(jax.ShapeDtypeStruct((batch, cfg.n_features), jnp.uint8))
    ys = place(jax.ShapeDtypeStruct((batch,), jnp.int32))
    key = place(jax.eval_shape(lambda: jax.random.key(0)))
    max_events = 2 * cfg.n_clauses * cfg.n_literals
    return jax.jit(train_step, static_argnames=("parallel", "max_events")
                   ).lower(bundle, xs, ys, key, None, parallel=False,
                           max_events=max_events).compile().as_text()


def test_train_step_phases_keep_their_names_for_v5e(v5e_train_step_hlo):
    """The chip's compiler keeps the step's phase scopes
    (``core/scopes.py``) in the compiled program's op names, and the
    scan of rounds is the one loop of the step, under ``tm.feedback``."""
    hlo = v5e_train_step_hlo
    segments = {seg for name in re.findall(r'op_name="([^"]*)"', hlo)
                for seg in name.split("/")}
    assert {scopes.FEEDBACK, scopes.DRAWS, scopes.EVENTS,
            scopes.CACHE_SYNC} <= segments
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    loops = re.findall(r' while\(.*op_name="([^"]*)"', entry)
    assert loops == [f"jit(train_step)/{scopes.FEEDBACK}/while"]


def test_event_selection_has_no_gather_for_v5e(v5e_train_step_hlo):
    """Event selection compiles for the chip without a gather: no gather
    instruction, fused or not, has an op name under ``tm.events``."""
    names = re.findall(r' gather\(.*op_name="([^"]*)"', v5e_train_step_hlo)
    assert not [name for name in names
                if scopes.EVENTS in name.split("/")]


def test_bitpack_step_selects_no_events_for_v5e(v5e_train_step_hlo):
    """The packed words are repacked from the new state, so the chip's
    program of a bitpack-only step has no sort or scatter, fused or not,
    under ``tm.events`` or ``tm.cache_sync``: the event buffer is gone."""
    names = re.findall(r' (?:scatter|sort)\(.*op_name="([^"]*)"',
                       v5e_train_step_hlo)
    assert not [name for name in names
                if {scopes.EVENTS, scopes.CACHE_SYNC} & set(name.split("/"))]


def test_i1_rounds_pack_nothing_for_v5e(one_chip):
    """The one-chip I1 train step as the benchmark runs it (32 samples,
    bitpack cache, Pallas rounds) evaluates each round's clauses straight
    from the int16 TA row: no op inside the scan's ``while`` has the
    ``u32[..., 10016]`` shape of the row's packed include bits, and both
    rounds call ``clause_outputs_rows``. The step's own repack of the
    bitpack words, after the scan, is the only pack left."""
    cfg = dataclasses.replace(I1, backend="pallas")
    batch = 32

    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    bundle = jax.tree.map(place, jax.eval_shape(
        lambda: init_bundle(cfg, engines=("bitpack",),
                            rng=jax.random.key(0))))
    xs = place(jax.ShapeDtypeStruct((batch, cfg.n_features), jnp.uint8))
    ys = place(jax.ShapeDtypeStruct((batch,), jnp.int32))
    key = place(jax.eval_shape(lambda: jax.random.key(0)))
    max_events = 2 * cfg.n_clauses * cfg.n_literals
    hlo = jax.jit(train_step, static_argnames=("parallel", "max_events")
                  ).lower(bundle, xs, ys, key, None, parallel=False,
                          max_events=max_events).compile().as_text()
    width = -(-cfg.n_literals // 32) * 32
    assert width == 10016
    packed = [ln for ln in hlo.splitlines()
              if re.search(rf"= u32\[[0-9,]*\b{width}\]", ln)]
    assert packed                              # the repack after the scan
    assert not [ln for ln in packed if "/while/body/" in ln]
    rounds = re.findall(r"^\s*%(clause_outputs\w*?)(?:\.\d+)? = .*"
                        r"custom-call\(", hlo, re.M)
    assert rounds == ["clause_outputs_rows"] * 2


def test_clause4_step_draws_only_its_own_rows_for_v5e(v5e, monkeypatch):
    """The sequential train step of I4 at 20,000 clauses, sharded by clause
    over the four chips, compiles for the chip: no op holds the full
    ``(20000, 40000)`` float32 draw, nothing under ``tm.draws`` gathers,
    both vote all-reduces of the rounds carry ``tm.votes``, and a chip's
    arguments and temporaries fit its 16 GB."""
    import numpy as np

    from repro.core import distributed

    cfg = TMConfig(n_classes=2, n_clauses=20000, n_features=20000,
                   n_states=127, s=27.0, threshold=40, backend="pallas")
    m, n, L = cfg.n_classes, cfg.n_clauses, cfg.n_literals
    batch, shards = 32, 4
    mesh = Mesh(np.asarray(v5e.devices).reshape(1, shards),
                ("data", "model"))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*axes)))

    # the step places its polarity on the mesh's devices, which are only
    # described here: hand it the placed shape instead
    pol = spec((n,), jnp.int32, "model")
    monkeypatch.setattr(distributed, "_sharded_polarity", lambda *_: pol)
    step = distributed.make_sharded_train_step(
        cfg, mesh, engines=("bitpack",),
        max_events=min(m, 2 * batch) * (n // shards) * L)
    compiled = step.jitted.lower(
        distributed.TMState(ta_state=spec((m, n, L), jnp.int16,
                                          None, "model", None)),
        {"bitpack": spec((m, n, -(-L // 32)), jnp.uint32,
                         None, "model", None)},
        pol, spec((batch, cfg.n_features), jnp.uint8, None, None),
        spec((batch,), jnp.int32, None), spec((2,), jnp.uint32, None),
        spec((batch,), jnp.bool_, None), spec((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "f32[20000,40000]" not in hlo
    assert "f32[5000,40000]" in hlo                 # the shard's own rows
    gathers = re.findall(r' gather\(.*op_name="([^"]*)"', hlo)
    assert not [g for g in gathers if scopes.DRAWS in g.split("/")]
    votes = [ln for ln in hlo.splitlines()
             if re.search(r"\ball-reduce(-start)?\(", ln)
             and "/while/body/" in ln]
    assert len(votes) == 2 and all(scopes.VOTES in ln for ln in votes)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
