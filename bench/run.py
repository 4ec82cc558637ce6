"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything the workload needs is found by name from ``BENCHMARK.json``
(``bench/harness.py``). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` profiles the window and reports its per-layer metrics, the
device's busy time and the ``breakdown``. The last line on stdout is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, [``breakdown``], ``checks``); the numbers compared for
``correct`` are also the last lines on stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace, work  # noqa: E402


def per_layer(cell: harness.Cell, outcome: harness.Outcome, peaks: dict,
              trace_dir: str):
    """(metrics, device fields, breakdown) of a traced run."""
    summary = trace.reduce(trace.load(trace_dir))
    ctx = SimpleNamespace(trace=summary, counters=outcome.counters,
                          tm=cell.tm, peaks=peaks, work=work)
    metrics = {}
    for i, entry in enumerate(cell.per_layer):
        reader = harness.load_module(entry["reader"], f"bench_metric_{i}")
        value = reader.read(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = {"busy_s": summary.busy_s, "window_s": summary.window_s}
    return metrics, device, summary.breakdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    try:
        devices = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peaks = harness.peaks(devices[0].device_kind)
    harness.enable_compile_cache()
    driver = harness.load_module(cell.driver, "bench_driver")
    trace_dir = harness.tmp_trace_dir() if args.trace else None
    try:
        outcome = driver.run(cell, args.seed, args.seconds, trace_dir,
                             devices)
        metrics, device, breakdown = {}, {}, None
        if args.trace:
            metrics, device, breakdown = per_layer(cell, outcome, peaks,
                                                   trace_dir)
        else:
            values = {**outcome.metrics,
                      "setup_s": outcome.window_start - T0}
            for entry in cell.end_to_end:
                metrics[entry["name"]] = {"value": values[entry["name"]],
                                          "unit": entry["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    result = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": outcome.memory_peak_bytes,
                   **device},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    harness.print_result(result, outcome.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
