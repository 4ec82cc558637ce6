"""``correct`` holds for the program and fails for the control and for every
fault a cell can have: the drivers run end to end at a small size on the
CPU (the harness's look for a chip is skipped), with the timed path broken
underneath by the patches of ``bench/control.py``."""
from __future__ import annotations

import jax
import pytest

from bench import control, harness

TM = {"n_classes": 3, "n_clauses": 32, "n_features": 60, "n_states": 127,
      "s": 3.9, "threshold": 8, "boost_true_positive": False,
      "empty_clause_output": 1}
CONFIG = {"tm": TM, "state": {"avg_clause_len": 8},
          "data": {"family": "binarized_images", "active": 0.3,
                   "noise": 0.05}}
TRAFFIC = {
    "serve_open_loop": {"driver": "serve_open_loop", "rows_per_s": 400,
                        "pool_rows": 64, "warm_requests": 8},
    "train_online": {"driver": "train_online", "batch": 8,
                     "pool_samples": 128, "warm_steps": 3, "depth": 2,
                     "engines": ["bitpack"]},
}
SEED = 2**31 + 99


def drive(kind: str, mode: str, seconds: float = 0.5) -> harness.Outcome:
    driver = harness.BENCH / "drivers" / f"{kind}.py"
    cell = harness.Cell(name=f"tiny_{kind}", chips=1, config=CONFIG,
                        traffic=TRAFFIC[kind], driver=driver,
                        end_to_end=[], per_layer=[])
    mod = harness.load_module(driver, f"tiny_{kind}")
    with control.patch(mode, kind, TM):
        return mod.run(cell, SEED, seconds, None, jax.devices())


def checks(out: harness.Outcome) -> dict:
    return {c.name: c.value for c in out.checks}


def test_serve_sound_run_is_correct():
    out = drive("serve_open_loop", "sound")
    assert out.correct, checks(out)
    assert out.attempted == 200 and out.failed == 0
    assert set(out.metrics) == {"serve_p95_ms", "serve_p50_ms",
                                "serve_rows_per_s"}


@pytest.mark.parametrize("mode", ["control", "fault_answer"])
def test_serve_control_and_fault_are_not_correct(mode):
    out = drive("serve_open_loop", mode)
    assert not out.correct
    assert checks(out)["score_gap_max"] >= 1


def test_train_sound_run_is_correct():
    out = drive("train_online", "sound", seconds=0.3)
    assert out.correct, checks(out)
    assert out.attempted > 0 and out.failed == 0
    assert set(out.metrics) == {"train_samples_per_s"}


@pytest.mark.parametrize("mode", ["control", "fault_unchanged",
                                  "fault_half"])
def test_train_control_and_faults_are_not_correct(mode):
    out = drive("train_online", mode, seconds=0.3)
    assert not out.correct
    assert checks(out)["ta_cells_differ"] > 0
