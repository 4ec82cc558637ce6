"""Ragged data×clause sharding (DESIGN.md §9): any topology composes.

Fast tests pin the pure resolution table (``distributed.clause_geometry``),
the per-shard row census and the ceil-based per-shard index capacity. The slow subprocess is the
acceptance property on a forced **4-device** host platform: a previously
indivisible topology (``data_shards=2 × clause_shards=2`` on ``n_clauses``
whose per-shard slice does not divide by the data ranks) trains via
hierarchical composition **bit-exactly** with ``Topology(1)``, in both
learning modes, under both the ``xla`` and ``pallas_interpret`` kernel
backends — and the session reports the ``composed_ragged`` rule, never the
replication fallback.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import indexing
from repro.core.distributed import (
    COMPOSED_EVEN, COMPOSED_RAGGED, REPLICATED, clause_geometry)

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# Resolution table (pure — no devices)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n_clauses,c,d,n_local,n_padded,n_sub,rule",
    [
        # PR-3 even composition unchanged
        (16, 4, 2, 4, 16, 2, COMPOSED_EVEN),
        (256, 4, 2, 64, 256, 32, COMPOSED_EVEN),
        # ragged sub-slices: n_local does not divide by data_shards
        (128, 3, 2, 43, 129, 22, COMPOSED_RAGGED),   # ISSUE acceptance shape
        (130, 2, 2, 65, 130, 33, COMPOSED_RAGGED),
        (10, 2, 4, 5, 10, 2, COMPOSED_RAGGED),       # pure-padding rank
        (14, 2, 3, 7, 14, 3, COMPOSED_RAGGED),       # prime per-shard count
        # escape hatch: more data ranks than clause rows → replicate
        (6, 2, 4, 3, 6, 3, REPLICATED),
        (2, 1, 4, 2, 2, 2, REPLICATED),
        # no data axis → nothing to compose
        (6, 2, 1, 3, 6, 3, "clause_only"),
        (10, 3, 1, 4, 12, 4, "clause_only"),         # ragged clause axis
    ],
)
def test_clause_geometry_table(n_clauses, c, d, n_local, n_padded, n_sub,
                               rule):
    g = clause_geometry(n_clauses, c, d)
    assert (g.n_local, g.n_padded, g.n_sub, g.composition) == (
        n_local, n_padded, n_sub, rule)
    assert g.ragged_clauses == (n_padded != n_clauses)
    if g.composes:
        # every real clause row is owned by exactly one (data, shard) slot
        assert d * g.n_sub >= g.n_local
        assert (d - 1) * g.n_sub < g.n_sub_padded
    assert g.n_sub_padded >= g.n_local


def test_shard_rows_census():
    # even: no padding anywhere
    g = clause_geometry(16, 4, 1)
    assert g.shard_rows() == [
        {"shard": i, "real_rows": 4, "pad_rows": 0} for i in range(4)]
    # ragged: padding lands entirely on the trailing shard(s)
    g = clause_geometry(10, 4, 1)  # n_local=3 -> rows 3,3,3,1(+2 pad)
    assert g.shard_rows() == [
        {"shard": 0, "real_rows": 3, "pad_rows": 0},
        {"shard": 1, "real_rows": 3, "pad_rows": 0},
        {"shard": 2, "real_rows": 3, "pad_rows": 0},
        {"shard": 3, "real_rows": 1, "pad_rows": 2}]
    assert sum(r["real_rows"] for r in g.shard_rows()) == 10


def test_shard_capacity_is_ceil():
    assert indexing.shard_capacity(128, 4) == 32      # divisible: unchanged
    assert indexing.shard_capacity(128, 3) == 43      # ragged: ceil
    assert indexing.shard_capacity(10, 4) == 3
    # per-shard worst case (its clause count) is always covered
    for n, s in [(128, 3), (10, 4), (7, 2), (6, 5)]:
        assert indexing.shard_capacity(n, s) >= -(-n // s)


def test_partitioning_declares_clause_padding():
    """The kernel contract names how each primitive tolerates padding rows
    (the §9 conventions the sharded wiring realises)."""
    from repro.kernels import backend as kbackend

    pad = {name: kbackend.get_primitive(name).partitioning.clause_padding
           for name in kbackend.registered_primitives()}
    assert pad["clause_votes"] == "zero_polarity"
    assert pad["ta_update"] == "masked_active"
    assert pad["clause_outputs"] == "caller_sliced"
    assert pad["clause_outputs_rows"] == "caller_sliced"


# ---------------------------------------------------------------------------
# Acceptance: forced-4-device subprocess, both backends, both modes
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.core import (
        TMConfig, Topology, TsetlinMachine, registered_engines)

    # n_clauses=6 over clause_shards=2 -> n_local=3; data_shards=2 does not
    # divide it -> the PR-3 path silently replicated; now: composed_ragged
    # (rank 0 owns 2 rows, rank 1 owns 1 row + 1 padding row per shard)
    cfg = TMConfig(n_classes=3, n_clauses=6, n_features=10, n_states=50,
                   s=3.0, threshold=4)
    ALL = cfg.n_classes * cfg.n_clauses * cfg.n_literals
    ragged = Topology(data_shards=2, clause_shards=2)
    rng = np.random.default_rng(0)
    # 20 samples at batch_size=8 -> trailing partial batch pads under a mask
    xs = jnp.asarray(rng.integers(0, 2, (20, 10)), jnp.uint8)
    ys = jnp.asarray(rng.integers(0, 3, 20), jnp.int32)
    xe = jnp.asarray(rng.integers(0, 2, (8, 10)), jnp.uint8)

    for parallel in (False, True):
        ref = TsetlinMachine(cfg, topology=Topology(), parallel=parallel,
                             max_events_per_batch=ALL, seed=7).init()
        ref.fit(xs, ys, epochs=2, batch_size=8)
        ref_ta = np.asarray(ref.state.ta_state)
        ref_pred = np.asarray(ref.predict(xe, engine="dense"))
        for backend in ("xla", "pallas_interpret"):
            topo = dataclasses.replace(ragged, backend=backend)
            m = TsetlinMachine(cfg, topology=topo, parallel=parallel,
                               max_events_per_batch=ALL, seed=7).init()
            d = m.session.describe()
            want_rule = "batch_parallel" if parallel else "composed_ragged"
            assert d["composition"] == want_rule, d
            assert d["backend"] == backend, d
            m.fit(xs, ys, epochs=2, batch_size=8)
            tag = f"{backend} parallel={parallel}"
            np.testing.assert_array_equal(
                np.asarray(m.state.ta_state), ref_ta, err_msg=tag)
            assert m.event_overflow == 0, tag
            for engine in registered_engines():
                np.testing.assert_array_equal(
                    np.asarray(m.predict(xe, engine=engine)), ref_pred,
                    err_msg=f"{tag}/{engine}")
    print("tm-ragged-parity-ok")
""")


@pytest.mark.slow
def test_tm_ragged_composition_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "tm-ragged-parity-ok" in res.stdout, (
        res.stdout + "\n" + res.stderr)
