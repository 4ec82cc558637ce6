"""Offered-rate sweep of a serve cell: the knee from which its fixed rate is
taken (once, on the chip; the rate then lives in the traffic file).

    python3 bench/sweep.py --workload <serve cell> --rates 1000,2000,4000 [--seconds 5] [--seed 1]

Builds the cell's server once, then offers each rate open-loop for
``--seconds`` (``drivers/serve_open_loop.offer``) and prints one JSON line
per rate: offered and achieved rows/s, p50 and p95 from the due time,
rejections, mean rows per batch and the generator's lag. It stops after
the first rate with more than 1% of its requests rejected or missing.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.gen import data  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated rows/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    harness.require_chip(cell.chips)
    harness.enable_compile_cache()
    driver = harness.load_module(cell.driver, "bench_driver")
    server, x_pool, _, engine = driver.build(cell, args.seed)
    rng = data.rng_for(args.seed, 5)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            w = driver.offer(server, x_pool, rate, args.seconds, rng)
            lost = w["rejected"] + w["missing"]
            print(json.dumps({
                "workload": args.workload, "engine": engine,
                "offered_rows_per_s": rate,
                "achieved_rows_per_s": w["serve_rows_per_s"],
                "serve_p50_ms": w["serve_p50_ms"],
                "serve_p95_ms": w["serve_p95_ms"],
                "rejected": w["rejected"], "missing": w["missing"],
                "rows_per_batch": w["rows_real"] / max(w["batches"], 1),
                "gen_lag_p95_ms": w["gen_lag_p95_ms"]}), flush=True)
            if lost > 0.01 * w["attempted"]:
                break
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
