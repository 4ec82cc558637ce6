"""Every train step carries the four phase scopes (``core/scopes.py``),
and the sharded sync step names its vote psum ``tm.votes``.

A device profile attributes an op's time to a phase by the scope in the
op's ``op_name``, so a refactor that drops a scope fails here instead of
quietly leaving a phase unnamed. Each step is lowered and compiled for a
tiny config, and each scope must appear in the ``op_name`` metadata of the
compiled HLO: the single-device step in both learning modes, and the
sharded step on a forced 4-device host mesh in four layouts: both modes
on data=2 × model=2 (the sequential step composes data × clause), the
clause-only 1 × 4 bitpack step the four-chip benchmark runs, and a clause
count the 2 × 2 mesh does not divide (ragged composition). Every
all-reduce that the per-round vote psum lowers to carries ``tm.votes``,
every collective of a sharded step is an all-reduce, and the
single-device step has no vote psum and carries no ``tm.votes``. A step
whose only cache is the packed words, single-device or clause-sharded,
compiles no sort or scatter under ``tm.events`` or ``tm.cache_sync``: the
words are repacked, so the event buffer is dead code.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import scopes

SRC = str(Path(__file__).resolve().parents[1] / "src")
SCOPES = (scopes.FEEDBACK, scopes.DRAWS, scopes.EVENTS, scopes.CACHE_SYNC)


def scopes_in(names) -> list[str]:
    """The scopes that name a segment of some op's name stack, bare or
    under a transform (``vmap(tm.draws)``)."""
    return [s for s in SCOPES
            if any(re.search(rf"(^|/)(\w+\()*{re.escape(s)}\)*(/|$)", n)
                   for n in names)]


def op_names(hlo: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def test_scope_names_are_distinct_and_namespaced():
    names = SCOPES + (scopes.VOTES,)
    assert len(set(names)) == 5
    assert all(s.startswith("tm.") for s in names)


@pytest.mark.parametrize("parallel", [False, True],
                         ids=["sequential", "parallel"])
def test_single_device_step_carries_every_scope(parallel):
    import jax
    import jax.numpy as jnp

    from repro.core import TMConfig, init_bundle, train_step

    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=6, n_states=20,
                   s=3.0, threshold=4)
    bundle = init_bundle(cfg, engines=("bitpack", "indexed"),
                         rng=jax.random.key(0))
    xs = jnp.zeros((4, 6), jnp.uint8)
    ys = jnp.zeros((4,), jnp.int32)
    step = jax.jit(train_step, static_argnames=("parallel", "max_events"))
    hlo = step.lower(bundle, xs, ys, jax.random.key(1), None,
                     parallel=parallel, max_events=32).compile().as_text()
    assert scopes_in(op_names(hlo)) == list(SCOPES)
    assert not any(scopes.VOTES in n for n in op_names(hlo))
    assert not all_reduces(hlo)


def event_path_ops(hlo: str) -> list[str]:
    """The ``op_name`` of every sort or scatter instruction, fused or not,
    under ``tm.events`` or ``tm.cache_sync``."""
    names = re.findall(r' (?:scatter|sort)\(.*op_name="([^"]*)"', hlo)
    return [n for n in names
            if {scopes.EVENTS, scopes.CACHE_SYNC} & set(scopes_in([n]))]


@pytest.mark.parametrize("engines,selects", [
    (("bitpack",), False), (("bitpack", "indexed"), True)],
    ids=["bitpack", "bitpack_indexed"])
def test_event_buffer_compiles_only_for_an_event_cache(engines, selects):
    """The packed words are repacked from the new state, so a bitpack-only
    step reads no event: its buffer's sort and scatters compile away, and
    only its overflow count stays. An index in the bundle keeps them."""
    import jax
    import jax.numpy as jnp

    from repro.core import TMConfig, init_bundle, train_step

    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=6, n_states=20,
                   s=3.0, threshold=4)
    bundle = init_bundle(cfg, engines=engines, rng=jax.random.key(0))
    xs = jnp.zeros((4, 6), jnp.uint8)
    ys = jnp.zeros((4,), jnp.int32)
    step = jax.jit(train_step, static_argnames=("parallel", "max_events"))
    hlo = step.lower(bundle, xs, ys, jax.random.key(1), None,
                     max_events=cfg.n_classes * cfg.n_clauses
                     * cfg.n_literals).compile().as_text()
    assert bool(event_path_ops(hlo)) == selects
    assert scopes_in(op_names(hlo)) == list(SCOPES)


def all_reduces(hlo: str) -> list[str]:
    """The ``op_name`` of every all-reduce instruction of a compiled
    module ("" where it has none)."""
    out = []
    for ln in hlo.splitlines():
        if re.search(r"\ball-reduce(-start)?\(", ln):
            m = re.search(r'op_name="([^"]*)"', ln)
            out.append(m.group(1) if m else "")
    return out


SHARDED = textwrap.dedent("""
    import dataclasses, json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro.core import TMConfig, init_tm
    from repro.core.distributed import (
        make_sharded_prepare, make_sharded_train_step)
    from repro.launch.mesh import make_host_mesh

    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=6, n_states=20,
                   s=3.0, threshold=4)
    engines = ("bitpack", "indexed")
    mesh = make_host_mesh(data=2, model=2)
    xs = jnp.zeros((4, 6), jnp.uint8)
    ys = jnp.zeros((4,), jnp.int32)
    key = jax.random.key_data(jax.random.key(1))
    mask = jnp.ones((4,), bool)
    out = {}

    def compile_step(name, cfg, mesh, engines, parallel=False,
                     composition=None):
        step = make_sharded_train_step(cfg, mesh, engines=engines,
                                       parallel=parallel, max_events=32)
        if composition is not None:
            assert step.composition == composition, step.composition
        b = make_sharded_prepare(cfg, mesh, engines=engines)(
            init_tm(cfg, jax.random.key(0)))
        hlo = step.jitted.lower(
            b.state, b.caches, step.pol, xs, ys, key, mask,
            jnp.zeros((), jnp.int32)).compile().as_text()
        out[name] = sorted(set(re.findall(r'op_name="([^"]*)"', hlo)))
        out[name + "_hlo"] = hlo

    compile_step("sequential_sync", cfg, mesh, engines)
    compile_step("parallel_sync", cfg, mesh, engines, parallel=True)
    # the clause-sharded bitpack-only step on the 2 x 2 mesh
    compile_step("sequential_sync_bitpack", cfg, mesh, ("bitpack",))
    # the four-chip benchmark's layout: clause-only, bitpack only
    compile_step("clause4_sync", cfg, make_host_mesh(data=1, model=4),
                 ("bitpack",), composition="clause_only")
    # 6 clauses: 3 a clause shard, which 2 data ranks cannot split evenly
    compile_step("ragged_sync", dataclasses.replace(cfg, n_clauses=6), mesh,
                 engines, composition="composed_ragged")
    print("OPNAMES " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_op_names():
    res = subprocess.run(
        [sys.executable, "-c", SHARDED],
        env={**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("OPNAMES ")][-1]
    return json.loads(line[len("OPNAMES "):])


SHARDED_BODIES = ["sequential_sync", "parallel_sync", "clause4_sync",
                  "ragged_sync"]


@pytest.mark.parametrize("body", SHARDED_BODIES)
def test_sharded_step_bodies_carry_every_scope(sharded_op_names, body):
    assert scopes_in(sharded_op_names[body]) == list(SCOPES)


@pytest.mark.parametrize("body,n_others", [
    ("sequential_sync", 2), ("parallel_sync", 2), ("clause4_sync", 1),
    ("ragged_sync", 2)],
    ids=["sequential_sync", "parallel_sync", "clause4_sync", "ragged_sync"])
def test_vote_psum_all_reduces_carry_the_votes_scope(sharded_op_names, body,
                                                     n_others):
    ops = all_reduces(sharded_op_names[body + "_hlo"])
    # the rounds' all-reduces: inside the scan's body, or under the vmap
    in_rounds = [n for n in ops if "/while/body/" in n or "vmap(" in n]
    assert len(in_rounds) == 2, ops          # one per class round
    assert all(re.search(rf"(^|/)(\w+\()*{re.escape(scopes.VOTES)}\)*/", n)
               for n in in_rounds), in_rounds
    # the step's other all-reduces are not votes: the overflow count, and
    # where the mesh has data ranks the state reassembly or delta sum
    others = [n for n in ops if n not in in_rounds]
    assert len(others) == n_others, ops
    assert not any(scopes.VOTES in n for n in others)


@pytest.mark.parametrize("body", SHARDED_BODIES)
def test_sharded_train_step_collectives_are_all_reduces(sharded_op_names,
                                                        body):
    hlo = sharded_op_names[body + "_hlo"]
    for kind in ("all-gather", "all-to-all", "collective-permute",
                 "reduce-scatter"):
        assert not re.search(rf"\b{kind}(-start)?\(", hlo), kind
    assert all_reduces(hlo)


@pytest.mark.parametrize("body,selects", [
    ("sequential_sync_bitpack", False), ("sequential_sync", True)],
    ids=["bitpack", "bitpack_indexed"])
def test_sharded_event_buffer_compiles_only_for_an_event_cache(
        sharded_op_names, body, selects):
    hlo = sharded_op_names[body + "_hlo"]
    assert bool(event_path_ops(hlo)) == selects
    assert scopes_in(op_names(hlo)) == list(SCOPES)
