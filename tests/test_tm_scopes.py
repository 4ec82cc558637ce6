"""Every train step carries the four phase scopes (``core/scopes.py``),
and the sharded sync step names its vote psum ``tm.votes``.

A device profile attributes an op's time to a phase by the scope in the
op's ``op_name``, so a refactor that drops a scope fails here instead of
quietly leaving a phase unnamed. Each step is lowered and compiled for a
tiny config, and each scope must appear in the ``op_name`` metadata of the
compiled HLO: the single-device step in both learning
modes, and both shard-local bodies (sync and stale-vote) of the sharded
step in both modes, on a forced 4-device host mesh (data=2 × model=2, so
the sequential step composes data × clause). Every all-reduce that the
per-round vote psum lowers to carries ``tm.votes``; the stale-vote and
single-device bodies have no vote psum and carry no ``tm.votes``. A step
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
    import json, os, re
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
    state = init_tm(cfg, jax.random.key(0))
    xs = jnp.zeros((4, 6), jnp.uint8)
    ys = jnp.zeros((4,), jnp.int32)
    key = jax.random.key_data(jax.random.key(1))
    mask = jnp.ones((4,), bool)
    out = {}
    for parallel in (False, True):
        for k in (0, 2):
            step = make_sharded_train_step(
                cfg, mesh, engines=engines, parallel=parallel,
                max_events=32, async_votes=k)
            b = make_sharded_prepare(cfg, mesh, engines=engines,
                                     async_votes=k)(state)
            if k:
                args = (b.state, b.caches, step.pol, b.vote_acc, xs, ys,
                        key, mask)
            else:
                args = (b.state, b.caches, step.pol, xs, ys, key, mask,
                        jnp.zeros((), jnp.int32))
            hlo = step.jitted.lower(*args).compile().as_text()
            name = (("parallel" if parallel else "sequential")
                    + ("_async" if k else "_sync"))
            out[name] = sorted(set(re.findall(r'op_name="([^"]*)"', hlo)))
            out[name + "_hlo"] = hlo
    # the clause-sharded bitpack-only step, as the four-chip benchmark runs
    step = make_sharded_train_step(cfg, mesh, engines=("bitpack",),
                                   max_events=32)
    b = make_sharded_prepare(cfg, mesh, engines=("bitpack",))(state)
    out["sequential_sync_bitpack_hlo"] = step.jitted.lower(
        b.state, b.caches, step.pol, xs, ys, key, mask,
        jnp.zeros((), jnp.int32)).compile().as_text()
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


@pytest.mark.parametrize("body", ["sequential_sync", "sequential_async",
                                  "parallel_sync", "parallel_async"])
def test_sharded_step_bodies_carry_every_scope(sharded_op_names, body):
    assert scopes_in(sharded_op_names[body]) == list(SCOPES)


@pytest.mark.parametrize("body", ["sequential_sync", "parallel_sync"])
def test_vote_psum_all_reduces_carry_the_votes_scope(sharded_op_names, body):
    ops = all_reduces(sharded_op_names[body + "_hlo"])
    # the rounds' all-reduces: inside the scan's body, or under the vmap
    in_rounds = [n for n in ops if "/while/body/" in n or "vmap(" in n]
    assert len(in_rounds) == 2, ops          # one per class round
    assert all(re.search(rf"(^|/)(\w+\()*{re.escape(scopes.VOTES)}\)*/", n)
               for n in in_rounds), in_rounds
    # the step's other all-reduces (overflow count, state reassembly or
    # delta sum) are not votes
    others = [n for n in ops if n not in in_rounds]
    assert len(others) == 2 and not any(scopes.VOTES in n for n in others)


@pytest.mark.parametrize("body", ["sequential_async", "parallel_async"])
def test_stale_vote_bodies_carry_no_votes_scope(sharded_op_names, body):
    assert not any(scopes.VOTES in n for n in sharded_op_names[body])


@pytest.mark.parametrize("body,selects", [
    ("sequential_sync_bitpack", False), ("sequential_sync", True)],
    ids=["bitpack", "bitpack_indexed"])
def test_sharded_event_buffer_compiles_only_for_an_event_cache(
        sharded_op_names, body, selects):
    hlo = sharded_op_names[body + "_hlo"]
    assert bool(event_path_ops(hlo)) == selects
    assert scopes_in(op_names(hlo)) == list(SCOPES)
