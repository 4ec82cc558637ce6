"""The clause-sharded train cell (``i4_train_clause4``): its driver at a tiny
size on four host CPU devices, its state generator, its readers on a
hand-built four-device trace, and the harness finding it by name.

The driver needs four devices, so it runs in a subprocess with
``--xla_force_host_platform_device_count=4``; the faults are
``bench/control.py``'s train patches, which replace
``TsetlinMachine.partial_fit`` whatever the topology."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from bench import harness, trace, work

ROOT = harness.ROOT
CELL = "i4_train_clause4"

SCRIPT = textwrap.dedent("""
    import contextlib, json, os
    from unittest import mock
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from bench import control, harness, ref
    from bench.gen import data, state

    TM = {"n_classes": 3, "n_clauses": 32, "n_features": 60,
          "n_states": 127, "s": 3.9, "threshold": 8,
          "boost_true_positive": False, "empty_clause_output": 1}
    CONFIG = {"tm": TM, "state": {"avg_clause_len": 8},
              "data": {"family": "binarized_images", "active": 0.3,
                       "noise": 0.05}}
    TRAFFIC = {"driver": "train_online_sharded", "batch": 8,
               "pool_samples": 128, "warm_steps": 3, "depth": 2,
               "engines": ["bitpack"], "clause_shards": 4}
    SEED = 2**31 + 99
    path = harness.BENCH / "drivers" / "train_online_sharded.py"
    driver = harness.load_module(path, "tiny_sharded")
    cell = harness.Cell(name="tiny_sharded", chips=4, config=CONFIG,
                        traffic=TRAFFIC, driver=path, end_to_end=[],
                        per_layer=[])
    out = {}
    modes = ("sound", "control", "fault_unchanged", "fault_half")
    for mode in modes + ("idle_reference",):
        # idle_reference: the machine learns nothing, and neither does the
        # reference, so the two agree on every cell
        with contextlib.ExitStack() as stack:
            if mode == "idle_reference":
                stack.enter_context(mock.patch.object(
                    ref, "train_steps", lambda ta, batches, keys, tm: [ta]))
                mode_patch = "fault_unchanged"
            else:
                mode_patch = mode
            stack.enter_context(control.patch(mode_patch, "train_online", TM))
            o = driver.run(cell, SEED, 0.3, None, jax.devices()[:4])
        out[mode] = {"correct": o.correct, "attempted": o.attempted,
                     "failed": o.failed, "metrics": o.metrics,
                     "counters": o.counters,
                     "checks": {c.name: c.value for c in o.checks}}

    # the sharded state generator against the single-device one, at a
    # clause count and width the four shards split unevenly into lanes
    tm = dict(TM, n_clauses=36, n_features=67)
    _, _, proto = data.pool(CONFIG["data"], 3, 67, 16, SEED)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("model",))
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, "model", None))
    got = driver.sharded_state(tm, proto, 8, SEED, sh)
    want = state.make_state(tm, proto, 8, SEED)
    out["state"] = {"equal": bool(jnp.array_equal(got, want)),
                    "shards": len(got.addressable_shards),
                    "shard_shape": list(got.addressable_shards[0].data.shape),
                    "dtype": str(got.dtype)}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def tiny():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=str(ROOT))
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_sound_run_is_correct(tiny):
    out = tiny["sound"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_samples_per_s"}
    assert out["counters"]["chips"] == 4
    assert out["counters"]["clauses_per_chip"] == 8
    # the reference learns in every warm step, so the check has teeth
    assert out["checks"]["reference_idle_steps"] == 0
    assert out["counters"]["reference_cells_changed_min"] > 0


@pytest.mark.parametrize("mode", ["control", "fault_unchanged",
                                  "fault_half"])
def test_sharded_control_and_faults_are_not_correct(tiny, mode):
    out = tiny[mode]
    assert not out["correct"]
    assert out["checks"]["ta_cells_differ"] > 0


def test_sharded_unchanged_fault_differs_by_what_the_reference_learns(tiny):
    # the unchanged machine differs from the reference by every cell the
    # reference changes, at least in the warm step that changes fewest
    assert (tiny["fault_unchanged"]["checks"]["ta_cells_differ"]
            >= tiny["sound"]["counters"]["reference_cells_changed_min"])


def test_sharded_run_whose_reference_learns_nothing_is_not_correct(tiny):
    out = tiny["idle_reference"]
    assert out["checks"]["ta_cells_differ"] == 0
    assert out["checks"]["reference_idle_steps"] == 3
    assert not out["correct"]


def test_sharded_state_equals_the_single_device_state(tiny):
    assert tiny["state"] == {"equal": True, "shards": 4,
                             "shard_shape": [3, 9, 134], "dtype": "int16"}


# ---------------------------------------------------------------------------
# Readers on a hand-built trace of four devices
# ---------------------------------------------------------------------------

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
TM = {"n_clauses": 20000, "n_features": 20000}


def summary(ops: dict) -> trace.TraceSummary:
    return trace.TraceSummary(
        window_s=20.0, busy_s=19.9, n_devices=4,
        ops={name: trace.OpTotal(name=name, text=name, seconds=s, count=c)
             for name, (s, c) in ops.items()},
        modules={}, gaps=[])


OPS = {
    # two vote all-reduces and an overflow one, seconds summed over chips
    "%psum.36 = s32[] all-reduce(s32[] %add.1), channel_id=1": (0.24, 1024),
    "%psum.37 = s32[] all-reduce(s32[] %add.2), channel_id=2": (0.12, 1024),
    "%all-reduce-start.1 = s32[] all-reduce-start(s32[] %x)": (0.03, 16),
    "%all-reduce-done.1 = s32[] all-reduce-done(s32[] %all-reduce-start.1)":
        (0.01, 16),
    "%ta_update = s16[5000,40000]{1,0} custom-call(s16[5000,40000] %p)":
        (4.0, 1024),
    "%fusion.3 = u32[400000000]{0} fusion(%psum.36), kind=kLoop": (9.0, 16),
    "%clause_outputs.2 = u8[5000]{0} custom-call(u32[5000,1250] %w)":
        (0.5, 2048),
}
COUNTERS = {"steps": 4, "samples": 128, "window_s": 20.0, "chips": 4,
            "clauses_per_chip": 5000}


def ctx(ops=OPS, counters=COUNTERS):
    return SimpleNamespace(trace=summary(ops), counters=counters, tm=TM,
                           peaks=PEAKS, work=work)


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"reader_{name}")


def test_vote_allreduce_ms_per_step_per_chip():
    # (0.24 + 0.12 + 0.03 + 0.01) s over 4 chips and 4 steps
    got = reader("train_vote_allreduce_ms").read(ctx())
    assert got == pytest.approx(1e3 * 0.40 / 4 / 4)


def test_sharded_mfu_counts_every_chip():
    # 128 samples in 20 s; 4·20000·40000 ops a sample; 4 chips' peak
    got = reader("train_sharded_mfu").read(ctx())
    assert got == pytest.approx(100 * 6.4 * 4 * 20000 * 40000 / (4 * 393e12))


def test_ta_update_shard_roofline_counts_the_shard_bytes():
    nbytes = 5000 * 40000 * 8 + 4 * 40000 + 4 * 5000
    got = reader("ta_update_shard_roofline").read(ctx())
    assert got == pytest.approx(100 * 1024 * nbytes / 819e9 / 4.0)
    assert 0 < got < 100


def test_clause_outputs_shard_roofline_counts_the_shard_bytes():
    words = 40000 // 32
    nbytes = 4 * 5000 * words + 4 * words + 5000
    got = reader("clause_outputs_shard_roofline").read(ctx())
    assert got == pytest.approx(100 * 2048 * nbytes / 819e9 / 0.5)
    assert 0 < got < 100


@pytest.mark.parametrize("name", ["train_vote_allreduce_ms",
                                  "train_sharded_mfu",
                                  "ta_update_shard_roofline",
                                  "clause_outputs_shard_roofline"])
def test_sharded_readers_read_none_where_nothing_ran(name):
    # a single-device program: no all-reduce, no kernel, no shard counters
    ops = {"%fusion.3 = u32[40]{0} fusion(%p), kind=kLoop": (1.0, 4)}
    assert reader(name).read(ctx(ops, {"steps": 4, "samples": 128,
                                       "window_s": 20.0})) is None


def test_harness_finds_the_sharded_cell():
    cell = harness.find_cell(CELL)
    assert cell.chips == 4
    assert cell.traffic["clause_shards"] == 4
    assert cell.driver.name == "train_online_sharded.py"
    assert cell.tm["n_clauses"] == 20000 and cell.tm["n_features"] == 20000
    assert cell.config["reduced"] == []
    # T scaled with the clause count from I1's T/n (2000 clauses, T = 40)
    assert cell.tm["threshold"] == 400
    assert {e["name"] for e in cell.end_to_end} == {"train_samples_per_s",
                                                    "setup_s"}
    assert {e["name"] for e in cell.per_layer} == {
        "train_device_idle", "train_vote_allreduce_ms",
        "train_sharded_mfu", "ta_update_shard_roofline",
        "clause_outputs_shard_roofline"}
    # the single-chip readers count the global clause axis against one
    # chip's peak: they do not list the sharded cell
    for other in ("i1_train_online", "m1_train_online"):
        names = {e["name"] for e in harness.find_cell(other).per_layer}
        assert "train_vote_allreduce_ms" not in names
