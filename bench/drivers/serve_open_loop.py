"""Open-loop serving: independent users send one row each, on a Poisson
schedule, to ``AsyncTMServer`` at its defaults.

Set-up (counted in ``setup_s``): the input pool and the TA state from the
seed, the session with the server's default engine only, ``prepare`` (that
engine's cache), the server's AOT buckets (compiled and warmed by the
server itself), and a short warm-up burst.

Window: the generator in this thread sleeps to each request's due time and
submits every due request. A request's latency runs from when it was due
to when its ``ScoreResult`` was in hand (``done_s``); a rejected request,
or one never answered, is a miss at +inf. After the window closes every
answer due in it is awaited (up to a minute past the close).

Correct: every answer is compared with the plain reference's Eq. 3 scores
of its row (``bench/ref.py``), computed after the server and its state are
freed; no answer may be missing and no program may be compiled or loaded
in the window.
"""
from __future__ import annotations

import gc
import inspect
import math
import time

import numpy as np

from bench import harness, ref
from bench.gen import arrivals, data, state

WAIT_AFTER_CLOSE_S = 60.0


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The ``q``-quantile by nearest rank (defined with +inf entries)."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(q * v.size) - 1)])


def default_engine(server_cls) -> str:
    """The engine a server serves when the caller names none."""
    return inspect.signature(server_cls).parameters["engine"].default


def build(cell: harness.Cell, seed: int):
    """Set-up: the pool, the state, the session and the started server
    (its AOT buckets compiled and warmed). Returns ``(server, x_pool,
    proto, engine)``."""
    from repro.core import TMSession, TMState, Topology
    from repro.core.types import TMConfig
    from repro.serving import AsyncTMServer

    tm = cell.tm
    x_pool, _, proto = data.pool(cell.config["data"], tm["n_classes"],
                                 tm["n_features"],
                                 cell.traffic["pool_rows"], seed)
    engine = default_engine(AsyncTMServer)
    ta = state.make_state(tm, proto, cell.config["state"]["avg_clause_len"],
                          seed)
    session = TMSession(TMConfig(**tm), Topology(engines=(engine,)))
    server = AsyncTMServer(session, session.prepare(TMState(ta_state=ta)))
    return server.start(), x_pool, proto, engine


def offer(server, x_pool: np.ndarray, rate: float, seconds: float,
          rng: np.random.Generator, trace_dir: str | None = None) -> dict:
    """One open-loop window at ``rate`` rows/s; waits for every answer due
    in it (up to a minute past the close). Returns the window's record."""
    from jax.profiler import TraceAnnotation

    from repro.serving import Overloaded, ScoreResult

    due = arrivals.poisson_fixed_count(rate, seconds, rng)
    rows = rng.integers(0, len(x_pool), due.size)
    n = due.size
    promises = [None] * n
    lag = np.zeros(n)
    with harness.traced(trace_dir):
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            window_start = time.monotonic()
            stats0 = server.stats()
            due_abs = t0 + due
            for i in range(n):
                now = time.perf_counter()
                if now < due_abs[i]:
                    with TraceAnnotation("bench.gen_wait"):
                        time.sleep(due_abs[i] - now)
                with TraceAnnotation("bench.submit"):
                    promises[i] = server.submit(x_pool[rows[i]])
                lag[i] = time.perf_counter() - due_abs[i]
            close = t0 + seconds
            now = time.perf_counter()
            if now < close:
                with TraceAnnotation("bench.gen_wait"):
                    time.sleep(close - now)
            stats1 = server.stats()

    give_up = time.monotonic() + WAIT_AFTER_CLOSE_S
    results = []
    for p in promises:
        try:
            results.append(p.wait(max(0.0, give_up - time.monotonic())))
        except TimeoutError:
            results.append(None)
    served = np.array([isinstance(r, ScoreResult) for r in results])
    latency = np.array([r.done_s - d if ok else np.inf
                        for r, d, ok in zip(results, due_abs, served)])
    return {
        "window_start": window_start,
        "attempted": n,
        "rows": rows,
        "served": served,
        "answers": [r.scores for r in results if isinstance(r, ScoreResult)],
        "rejected": sum(isinstance(r, Overloaded) for r in results),
        "missing": sum(r is None for r in results),
        "serve_p95_ms": nearest_rank(latency, 0.95) * 1e3,
        "serve_p50_ms": nearest_rank(latency, 0.50) * 1e3,
        "serve_rows_per_s": sum(ok and r.done_s <= close for r, ok
                                in zip(results, served)) / seconds,
        "gen_lag_p95_ms": nearest_rank(lag, 0.95) * 1e3,
        **{k: stats1[k] - stats0[k]
           for k in ("batches", "rows_real", "rows_padded")},
    }


def run(cell: harness.Cell, seed: int, seconds: float,
        trace_dir: str | None, devices) -> harness.Outcome:
    tm, tr = cell.tm, cell.traffic
    compiles = harness.CompileCounter()
    server, x_pool, proto, _ = build(cell, seed)
    rng = data.rng_for(seed, 5)
    warm = [server.submit(x_pool[i]) for i in
            rng.integers(0, len(x_pool), tr["warm_requests"])]
    for p in warm:
        p.wait(WAIT_AFTER_CLOSE_S)

    aot0 = server.aot.counters()
    compiles.active = True
    w = offer(server, x_pool, tr["rows_per_s"], seconds, rng, trace_dir)
    compiles.active = False
    aot1 = server.aot.counters()
    peak = harness.memory_peak_bytes(devices)
    server.stop()
    del server, warm
    gc.collect()

    # the reference: the state again from the seed, Eq. 3 for every pool
    # row that was served, compared with every answer
    ta = state.make_state(tm, proto, cell.config["state"]["avg_clause_len"],
                          seed)
    served_rows = w["rows"][w["served"]]
    want = np.zeros((len(x_pool), tm["n_classes"]), np.int64)
    used = np.unique(served_rows)
    if used.size:
        want[used] = ref.scores_blocked(ta, x_pool[used],
                                        n_states=tm["n_states"])
    del ta
    got = np.array(w["answers"], np.int64).reshape(-1, tm["n_classes"])
    score_gap = (float(np.max(np.abs(got - want[served_rows])))
                 if got.size else math.inf)

    return harness.Outcome(
        window_start=w["window_start"],
        attempted=w["attempted"],
        failed=w["rejected"] + w["missing"],
        metrics={k: w[k] for k in ("serve_p95_ms", "serve_p50_ms",
                                   "serve_rows_per_s")},
        checks=[
            harness.Check("score_gap_max", score_gap, 0),
            harness.Check("answers_missing", w["missing"], 0),
            harness.Check("window_programs",
                          compiles.count + aot1["lowerings"]
                          - aot0["lowerings"] + aot1["misses"], 0),
        ],
        counters={k: w[k] for k in ("batches", "rows_real", "rows_padded",
                                     "gen_lag_p95_ms")},
        memory_peak_bytes=peak,
    )
