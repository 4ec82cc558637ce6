"""Sharding tests on a multi-device host mesh (subprocess: 8 CPU devices).

Runs the real lowering path (param specs, activation constraints, the
flash-decode shard_map, the TM clause-sharded eval) on a 2×4 mesh and
checks numerical equivalence vs the unsharded path.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build
    from repro.sharding import Policy, named_shardings, param_specs
    from repro.steps import make_decode_step, make_train_step

    mesh = make_host_mesh(data=2, model=4)

    # ---- decode: sharded flash-decode == unsharded dense decode ----
    cfg = dataclasses.replace(
        get_config("qwen3-1.7b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab=256, remat=False)
    model = build(cfg)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        model.init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)

    pol_none = Policy.none()
    cache = model.init_cache(2, 16)
    logits_ref = None
    c = cache
    for i in range(8):
        logits_ref, c = model.decode_step(
            pol_none, params, toks[:, i:i+1], c,
            jnp.full((2,), i, jnp.int32))

    dshape = ShapeSpec("d", "decode", 16, 2)
    dstep = make_decode_step(cfg, dshape, mesh)
    in_sh = named_shardings(mesh, dstep.in_specs)
    out_sh = named_shardings(mesh, dstep.out_specs)
    with jax.set_mesh(mesh):
        fn = jax.jit(dstep.fn, in_shardings=in_sh, out_shardings=out_sh)
        c2 = jax.device_put(model.init_cache(2, 16), in_sh[1])
        p2 = jax.device_put(params, in_sh[0])
        for i in range(8):
            logits_sh, c2 = fn(
                p2, c2,
                jax.device_put(toks[:, i:i+1], in_sh[2]),
                jax.device_put(jnp.full((2,), i, jnp.int32), in_sh[3]))
    # TP splits contractions and the partial-softmax combine reorders
    # reductions — bf16 drift is expected; argmax must agree exactly.
    np.testing.assert_allclose(np.asarray(logits_sh),
                               np.asarray(logits_ref), rtol=0.1, atol=0.35)
    assert (np.argmax(np.asarray(logits_sh), -1)
            == np.argmax(np.asarray(logits_ref), -1)).all()
    print("decode-shard-ok")

    # ---- train: one sharded train step == one unsharded step ----
    from repro.optim import adamw, compression
    tshape = ShapeSpec("t", "train", 16, 4)
    tstep = make_train_step(cfg, tshape, mesh, microbatches=2,
                            peak_lr=1e-3, warmup_steps=0, total_steps=10)
    tstep_ref = make_train_step(cfg, tshape, None, microbatches=2,
                                peak_lr=1e-3, warmup_steps=0, total_steps=10)
    params32 = model.init(jax.random.key(1))
    state = {"params": params32, "opt": adamw.init(params32),
             "ef": compression.init_error_feedback(params32)}
    batch = {"tokens": jnp.asarray(rng.integers(0, 256, (4, 16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 256, (4, 16)), jnp.int32)}
    new_ref, m_ref = jax.jit(tstep_ref.fn)(state, batch)
    in_sh = named_shardings(mesh, tstep.in_specs)
    out_sh = named_shardings(mesh, tstep.out_specs)
    with jax.set_mesh(mesh):
        fns = jax.jit(tstep.fn, in_shardings=in_sh, out_shardings=out_sh)
        new_sh, m_sh = fns(jax.device_put(state, in_sh[0]),
                           jax.device_put(batch, in_sh[1]))
    np.testing.assert_allclose(float(m_sh["nll"]), float(m_ref["nll"]),
                               rtol=2e-2)
    # Adam at step 1 normalizes by sqrt(v)≈|g|: bf16 grad noise becomes
    # O(lr)-scale update differences (same bound as test_steps.py).
    w_ref = np.asarray(new_ref["params"]["layers"]["b0_attn_mlp"]["attn"]["wq"])
    w_sh = np.asarray(new_sh["params"]["layers"]["b0_attn_mlp"]["attn"]["wq"])
    np.testing.assert_allclose(w_sh, w_ref, rtol=0.5, atol=4e-3)
    print("train-shard-ok")

    # ---- MoE: shard_map engine == local engine ----
    from repro.models.moe import init_moe, moe_block
    pm = init_moe(jax.random.key(3), 32, 16, 4, n_shared=0)
    xm = jnp.asarray(rng.normal(size=(4, 8, 32)) * 0.3, jnp.float32)
    out_ref, aux_ref = moe_block(pm, xm, top_k=2, capacity_factor=1.5,
                                 policy=Policy.none())
    with jax.set_mesh(mesh):
        pol = Policy.for_mesh(mesh)
        pm_sh = jax.device_put(pm, NamedSharding(mesh, P()))
        fn = jax.jit(lambda p, x: moe_block(
            p, x, top_k=2, capacity_factor=1.5, policy=pol))
        out_sh, aux_sh = fn(pm_sh, jax.device_put(
            xm, NamedSharding(mesh, P("data", None, None))))
    np.testing.assert_allclose(np.asarray(out_sh), np.asarray(out_ref),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(float(aux_sh), float(aux_ref), rtol=1e-4)
    print("moe-shard-ok")

    # ---- GPipe pipeline schedule == sequential stack ----
    from repro.models.pipeline import gpipe_apply
    S, M, mb2, dpp = 2, 6, 2, 16
    Ws = jnp.asarray(np.random.default_rng(1).normal(size=(S, dpp, dpp)) * 0.3,
                     jnp.float32)
    xpp = jnp.asarray(np.random.default_rng(2).normal(size=(M, mb2, dpp)),
                      jnp.float32)
    stage = lambda W, x: jnp.tanh(x @ W)
    ref = xpp
    for si in range(S):
        ref = jax.vmap(lambda xm: stage(Ws[si], xm))(ref)
    with jax.set_mesh(mesh):
        outpp = jax.jit(lambda p, xx: gpipe_apply(
            stage, p, xx, mesh=mesh, axis="data"))(Ws, xpp)
    np.testing.assert_allclose(np.asarray(outpp), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    print("gpipe-ok")

    # ---- TM: clause-sharded bundle scores == local scores ----
    # (the full registry-driven engine/train parity matrix lives in
    #  tests/test_tm_sharded.py; this is the cross-stack smoke check)
    from repro.core import TMConfig, scores
    from repro.core.distributed import make_sharded_prepare, make_sharded_scores
    tmc = TMConfig(n_classes=4, n_clauses=32, n_features=24, n_states=40)
    rng2 = np.random.default_rng(7)
    ta = jnp.asarray(rng2.integers(1, 81, (4, 32, 48)), jnp.int16)
    xs = jnp.asarray(rng2.integers(0, 2, (8, 24)), jnp.uint8)
    from repro.core.types import TMState
    want = scores(tmc, TMState(ta_state=ta), xs)
    bundle = make_sharded_prepare(tmc, mesh)(TMState(ta_state=ta))
    got = make_sharded_scores(tmc, mesh, engine="dense")(bundle, xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("tm-shard-ok")
""")


@pytest.mark.slow
def test_sharded_equivalence_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for marker in ("decode-shard-ok", "train-shard-ok", "moe-shard-ok",
                   "gpipe-ok", "tm-shard-ok"):
        assert marker in res.stdout, res.stdout + "\n" + res.stderr
