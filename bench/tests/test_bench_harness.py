"""The harness finds every piece of a cell by name, refuses what is
missing, and never runs without a TPU."""
from __future__ import annotations

import copy
import json
import re

import pytest

from bench import harness, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_every_named_piece_is_found(bench):
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        assert cell.driver.is_file()
        assert cell.chips == w["chips"]
        assert "tm" in cell.config and "data" in cell.config
        names = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for entry in cell.per_layer:
            reader = harness.load_module(entry["reader"], "reader")
            assert callable(reader.read)


def test_every_metric_has_a_reader_and_every_config_a_cell(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for entry in bench["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{entry['name']}.py").is_file()


def test_benchmark_file_keeps_its_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for e in bench["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and UNIT.match(e["unit"])
        assert e["source"] in ("host_clock", "device_trace")
    for e in bench["per_layer"]:
        assert e["moves"] in e2e and UNIT.match(e["unit"])
        for w in e["workloads"]:
            assert w in e2e[e["moves"]].get("workloads", [w])
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_missing_piece_is_an_error(bench):
    with pytest.raises(KeyError):
        harness.find_cell("no_such_cell", bench)
    b = copy.deepcopy(bench)
    b["workloads"][0]["traffic"] = "no_such_traffic"
    with pytest.raises(FileNotFoundError):
        harness.find_cell(b["workloads"][0]["name"], b)
    b = copy.deepcopy(bench)
    b["workloads"][0]["config"] = "no_such_config"
    with pytest.raises(KeyError):
        harness.find_cell(b["workloads"][0]["name"], b)
    b = copy.deepcopy(bench)
    b["per_layer"].append({**b["per_layer"][0], "name": "no_such_metric",
                           "workloads": [b["workloads"][0]["name"]]})
    with pytest.raises(FileNotFoundError):
        harness.find_cell(b["workloads"][0]["name"], b)


def test_no_tpu_fails_and_prints_no_result(bench, capsys):
    with pytest.raises(harness.NoChip):
        harness.require_chip(1)
    rc = run.main(["--workload", bench["workloads"][0]["name"],
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_peaks_are_keyed_by_device_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
