"""Clause-sharded TMBundle parity on a forced 8-device host mesh.

Registry-driven (subprocess, ``--xla_force_host_platform_device_count=8``):

  * every registered engine's sharded ``scores`` is bit-exact vs the
    single-device dense reference;
  * the sharded ``train_step`` (sequential *and* batch-parallel) produces a
    bit-exact TA state vs the single-device ``api.train_step``, and every
    engine's shard-local cache stays a faithful mirror (scores parity after
    training proves the event sync);
  * ragged boundaries (DESIGN.md §9): a prime per-shard clause count whose
    data sub-slices carry more padding than real rows on some ranks trains
    and scores bit-exactly (``composed_ragged``), and the
    ``data_shards > n_local`` escape hatch warns, names the ``replicated``
    rule, and stays bit-exact;
  * the fault-tolerant trainer checkpoints a sharded TM bundle, crashes,
    and restores **onto a different mesh** (reshard-on-restore: 4 clause
    shards → 2), continuing bit-exactly vs an uninterrupted single-device
    trainer run.

On a forced 4-device mesh (subprocess): ``Topology(clause_shards=4)``,
whose shards draw only their own rows of the uniform stream, trains
bit-exactly with ``Topology(1)`` and with the benchmark's plain reference
(``bench/ref.py``), on an even and a ragged clause count; with an event
buffer that overflows, its packed words still equal a fresh pack.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.core import (
        TMConfig, TMSession, TMState, bundle_scores, init_bundle,
        registered_engines, train_step)
    from repro.core.distributed import (
        make_sharded_prepare, make_sharded_scores, make_sharded_train_step)
    from repro.launch.mesh import make_host_mesh

    cfg = TMConfig(n_classes=3, n_clauses=16, n_features=12, n_states=50,
                   s=3.0, threshold=4)
    ALL = cfg.n_classes * cfg.n_clauses * cfg.n_literals
    rng = np.random.default_rng(0)
    inc = rng.uniform(size=(3, 16, 24)) < 0.4
    state = TMState(ta_state=jnp.asarray(
        np.where(inc, cfg.n_states + 1, cfg.n_states), jnp.int16))
    xs_eval = jnp.asarray(rng.integers(0, 2, (8, 12)), jnp.uint8)

    mesh = make_host_mesh(data=2, model=4)
    ref = init_bundle(cfg, state=state)
    stm = TMSession(cfg, mesh=mesh, max_events=ALL)
    assert stm.describe() == {"clause_shards": 4, "data_shards": 2,
                              "devices": 8, "sharded": True,
                              "backend": "xla", "async_votes": 0,
                              "composition": "composed_even",
                              "shard_rows": [
                                  {"shard": i, "real_rows": 4, "pad_rows": 0}
                                  for i in range(4)]}, stm.describe()
    sb = stm.prepare(state)

    # ---- scores parity: every registered engine, bit-exact vs dense ----
    want = np.asarray(bundle_scores(ref, xs_eval, engine="dense"))
    for name in registered_engines():
        got = np.asarray(stm.scores(sb, xs_eval, engine=name))
        np.testing.assert_array_equal(got, want, err_msg=name)
    print("tm-scores-parity-ok")

    # ---- train parity: both learning modes, caches mirrored ----
    for parallel in (False, True):
        step = make_sharded_train_step(cfg, mesh, parallel=parallel,
                                       max_events=ALL)
        b_ref, b_sh = ref, stm.prepare(state)
        key = jax.random.key(1)
        for _ in range(3):
            key, sub = jax.random.split(key)
            bx = jnp.asarray(rng.integers(0, 2, (8, 12)), jnp.uint8)
            by = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)
            b_ref = train_step(b_ref, bx, by, sub, parallel=parallel,
                               max_events=ALL)
            b_sh = step(b_sh, bx, by, sub)
        np.testing.assert_array_equal(
            np.asarray(b_sh.state.ta_state), np.asarray(b_ref.state.ta_state),
            err_msg=f"parallel={parallel}")
        want2 = np.asarray(bundle_scores(b_ref, xs_eval, engine="dense"))
        for name in registered_engines():
            got2 = np.asarray(stm.scores(b_sh, xs_eval, engine=name))
            np.testing.assert_array_equal(
                got2, want2, err_msg=f"{name} parallel={parallel}")
    print("tm-train-parity-ok")

    # ---- ragged boundaries (DESIGN.md §9) ----
    import warnings

    # prime per-shard clause count with padding > real rows on a rank:
    # n_clauses=14 over model=2 -> n_local=7 (prime); data=3 -> n_sub=3,
    # so the last data rank owns 1 real row + 2 padding rows per shard
    cfg_p = TMConfig(n_classes=3, n_clauses=14, n_features=12, n_states=50,
                     s=3.0, threshold=4)
    ALLP = cfg_p.n_classes * cfg_p.n_clauses * cfg_p.n_literals
    inc_p = rng.uniform(size=(3, 14, 24)) < 0.4
    state_p = TMState(ta_state=jnp.asarray(
        np.where(inc_p, cfg_p.n_states + 1, cfg_p.n_states), jnp.int16))
    mesh_p = make_host_mesh(data=3, model=2)
    stm_p = TMSession(cfg_p, mesh=mesh_p, max_events=ALLP)
    assert stm_p.describe()["composition"] == "composed_ragged", (
        stm_p.describe())
    ref_p = init_bundle(cfg_p, state=state_p)
    b_p = stm_p.prepare(state_p)
    key = jax.random.key(2)
    for _ in range(2):
        key, sub = jax.random.split(key)
        bx = jnp.asarray(rng.integers(0, 2, (6, 12)), jnp.uint8)
        by = jnp.asarray(rng.integers(0, 3, 6), jnp.int32)
        ref_p = train_step(ref_p, bx, by, sub, max_events=ALLP)
        b_p = stm_p.train_step(b_p, bx, by, sub)
    np.testing.assert_array_equal(
        np.asarray(stm_p.unpad_state(b_p.state).ta_state),
        np.asarray(ref_p.state.ta_state))
    # eval batch must divide over the 3-way data axis (scores shard it)
    xe_p = xs_eval[:6]
    want_p = np.asarray(bundle_scores(ref_p, xe_p, engine="dense"))
    for name in registered_engines():
        np.testing.assert_array_equal(
            np.asarray(stm_p.scores(b_p, xe_p, engine=name)), want_p,
            err_msg=f"prime-ragged/{name}")
    print("tm-ragged-prime-ok")

    # escape hatch: data_shards=4 > n_local=3 (n_clauses=6 / model=2) ->
    # warn-and-replicate, naming the fired rule; still bit-exact
    cfg_r = TMConfig(n_classes=3, n_clauses=6, n_features=12, n_states=50,
                     s=3.0, threshold=4)
    ALLR = cfg_r.n_classes * cfg_r.n_clauses * cfg_r.n_literals
    mesh_r = make_host_mesh(data=4, model=2)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        stm_r = TMSession(cfg_r, mesh=mesh_r, max_events=ALLR)
    assert stm_r.describe()["composition"] == "replicated", stm_r.describe()
    assert any("'replicated'" in str(w.message)
               and "data_shards=4" in str(w.message) for w in caught), (
        [str(w.message) for w in caught])
    ref_r = init_bundle(cfg_r)
    b_r = stm_r.prepare(ref_r.state)
    key, sub = jax.random.split(key)
    bx = jnp.asarray(rng.integers(0, 2, (6, 12)), jnp.uint8)
    by = jnp.asarray(rng.integers(0, 3, 6), jnp.int32)
    ref_r = train_step(ref_r, bx, by, sub, max_events=ALLR)
    b_r = stm_r.train_step(b_r, bx, by, sub)
    np.testing.assert_array_equal(
        np.asarray(stm_r.unpad_state(b_r.state).ta_state),
        np.asarray(ref_r.state.ta_state))
    print("tm-ragged-replicate-ok")

    # ---- trainer: sharded checkpoint → crash → reshard-on-restore ----
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.runtime.tm_task import make_tm_task
    from repro.runtime.trainer import (
        SimulatedFailure, Trainer, TrainLoopConfig)

    def build(task, ckpt_dir, total, failure_at=None):
        return Trainer(step_fn=task.step_fn, state=task.state,
                       batcher=task.batcher,
                       checkpointer=Checkpointer(ckpt_dir, keep=10),
                       loop=TrainLoopConfig(total_steps=total, ckpt_every=3,
                                            log_every=1,
                                            failure_at=failure_at),
                       to_ckpt=task.to_ckpt, from_ckpt=task.from_ckpt)

    tmp = tempfile.mkdtemp()
    kw = dict(batch=8, seed=3, data_seed=11, max_events=ALL)

    ref_tr = build(make_tm_task(cfg, **kw), tmp + "/ref", 8)
    ref_tr.run()
    ref_ta = np.asarray(ref_tr.state["bundle"].state.ta_state)

    tr = build(make_tm_task(cfg, mesh=mesh, **kw), tmp + "/ft", 8,
               failure_at=5)
    try:
        tr.run()
        raise AssertionError("expected injected failure")
    except SimulatedFailure:
        pass

    mesh2 = make_host_mesh(data=4, model=2)   # different clause-shard count
    tr2 = build(make_tm_task(cfg, mesh=mesh2, **kw), tmp + "/ft", 8)
    resumed = tr2.restore_if_available()
    assert resumed == 3, resumed
    tr2.run(start_step=resumed)
    np.testing.assert_array_equal(
        np.asarray(tr2.state["bundle"].state.ta_state), ref_ta)
    # the rebuilt shard-local caches on mesh2 serve identical scores
    stm2 = TMSession(cfg, mesh=mesh2, max_events=ALL)
    want3 = np.asarray(bundle_scores(ref_tr.state["bundle"], xs_eval,
                                     engine="dense"))
    for name in registered_engines():
        got3 = np.asarray(stm2.scores(tr2.state["bundle"], xs_eval,
                                      engine=name))
        np.testing.assert_array_equal(got3, want3, err_msg=name)
    print("tm-trainer-reshard-ok")
""")


@pytest.mark.slow
def test_tm_sharded_parity_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for marker in ("tm-scores-parity-ok", "tm-train-parity-ok",
                   "tm-ragged-prime-ok", "tm-ragged-replicate-ok",
                   "tm-trainer-reshard-ok"):
        assert marker in res.stdout, res.stdout + "\n" + res.stderr


# Per-shard draws: each clause shard draws only its own rows of the uniform
# stream (tm.uniform_rows); training stays bit-exact with one device and with
# the benchmark's plain reference, which draws the full (n, 2o) uniforms.
PER_SHARD = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import TMConfig, TMState, Topology, TsetlinMachine
    from repro.core.bitpack import pack_bits
    from repro.core.types import include_mask
    from bench import ref

    out = {}
    for n_clauses in (16, 18):        # 4 rows a shard; 5 with 2 padding rows
        tm = dict(n_classes=3, n_clauses=n_clauses, n_features=35,
                  n_states=50, s=3.0, threshold=4,
                  boost_true_positive=False)
        cfg = TMConfig(**tm)
        rng = np.random.default_rng(n_clauses)
        ta0 = jnp.asarray(rng.integers(40, 61, (3, n_clauses, 70)),
                          jnp.int16)
        batches = [(rng.integers(0, 2, (6, 35)).astype(np.uint8),
                    rng.integers(0, 3, 6).astype(np.int32))
                   for _ in range(3)]
        keys = [jax.random.key(100 + k) for k in range(3)]
        states = {}
        for name, topo in (("one", Topology()),
                           ("clause4", Topology(clause_shards=4))):
            machine = TsetlinMachine(cfg, topology=topo,
                                     engines=("bitpack",),
                                     max_events_per_batch=3 * 20 * 70)
            machine.bundle = machine.session.prepare(TMState(ta_state=ta0))
            got = []
            for (xs, ys), k in zip(batches, keys):
                machine.partial_fit(xs, ys, rng=k)
                got.append(np.asarray(machine.state.ta_state))
            states[name] = got
        want = [np.asarray(a) for a in ref.train_steps(ta0, batches, keys, tm)]
        # a 1-slot buffer per shard overflows; the words are repacked
        tight = TsetlinMachine(cfg, topology=Topology(clause_shards=4),
                               engines=("bitpack",), max_events_per_batch=1)
        tight.bundle = tight.session.prepare(TMState(ta_state=ta0))
        tight.partial_fit(*batches[0], rng=keys[0])
        words = pack_bits(include_mask(cfg, tight.bundle.state))
        out[n_clauses] = {
            "tight_overflow": tight.event_overflow,
            "tight_words_differ": int(
                (np.asarray(tight.bundle.caches["bitpack"])
                 != np.asarray(words)).sum()),
            "clause4_vs_one": [int((a != b).sum()) for a, b in
                               zip(states["clause4"], states["one"])],
            "clause4_vs_ref": [int((a != b).sum()) for a, b in
                               zip(states["clause4"], want)],
            "changed": [int((a != np.asarray(ta0)).sum())
                        for a in states["clause4"]]}
    print("PERSHARD " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def per_shard_runs():
    root = str(Path(__file__).resolve().parents[1])
    res = subprocess.run(
        [sys.executable, "-c", PER_SHARD],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": SRC + ":" + root},
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("PERSHARD ")][-1]
    return json.loads(line[len("PERSHARD "):])


@pytest.mark.parametrize("n_clauses", ["16", "18"], ids=["even", "ragged"])
def test_clause4_training_equals_one_device_and_the_reference(
        per_shard_runs, n_clauses):
    run = per_shard_runs[n_clauses]
    assert run["clause4_vs_one"] == [0, 0, 0]
    assert run["clause4_vs_ref"] == [0, 0, 0]
    assert all(c > 0 for c in run["changed"])      # the steps learnt


@pytest.mark.parametrize("n_clauses", ["16", "18"], ids=["even", "ragged"])
def test_clause4_overflowing_step_keeps_exact_words(per_shard_runs,
                                                     n_clauses):
    """Each shard repacks its own rows: with a 1-slot event buffer the step
    counts dropped crossings, and the packed words equal a fresh pack of
    the new sharded state."""
    run = per_shard_runs[n_clauses]
    assert run["tight_overflow"] > 0
    assert run["tight_words_differ"] == 0
