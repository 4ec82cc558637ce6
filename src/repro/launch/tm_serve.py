"""TM serving benchmark CLI — a thin layer over ``repro.serving``.

    PYTHONPATH=src python -m repro.launch.tm_serve --smoke
    PYTHONPATH=src python -m repro.launch.tm_serve \
        --engine indexed,bitpack_xla --requests 2048 --rps 4000
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.tm_serve --data-shards 4

The serving runtime itself lives in ``src/repro/serving/`` (DESIGN.md
§10): an AOT bucket cache compiles every padding bucket up front
(``serving/aot.py``), ``AsyncTMServer`` overlaps host batching with
device compute behind bounded-backlog admission control and per-tenant
fairness (``serving/runtime.py``), and an open-loop Poisson load
generator sweeps offered rates (``serving/loadgen.py``). This module only
builds sessions, drives the benchmark, and writes the record.

``BENCH_tm_serve.json`` (schema 2, docs/BENCH_SCHEMAS.md; gitignored
scratch) contains:

  * ``engines`` — the legacy closed-loop records from ``serve_engine``:
    a simulated arrival clock advanced by *measured* compute times
    (deterministic per seed, no sleeps). Kept for latency-percentile
    tracking across PRs; its "throughput" splices compute windows
    end-to-end and is **not** wall-clock comparable (DESIGN.md §10).
  * ``sustained_load`` — the open-loop comparison: a ``SyncTMServer``
    (the old blocking loop behind the modern submit surface) is ramped to
    saturation, then ``AsyncTMServer`` sweeps an offered-rate ladder
    around that baseline. Same load generator, same wall clock, so
    ``knee_exceeds_sync`` is a fair apples-to-apples claim.
  * ``batch_axis_scaling`` — the same load at 1, 2, … data shards when
    more than one device is available.

The CI smoke (scripts/ci.sh) runs under a forced 4-device host platform
with ``--backend pallas_interpret`` and asserts the record's shape,
including a well-formed ``sustained_load`` with zero hot-loop compiles.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TMConfig, TMState, registered_engines
from repro.core.session import TMSession, Topology
from repro.data.synthetic import binarized_images
from repro.serving import (
    AOTBucketCache, AsyncTMServer, SyncTMServer, bucket_for, buckets,
    run_step, sustained_load)
from repro.serving.loadgen import holds


@dataclasses.dataclass(frozen=True)
class ServePolicy:
    max_batch: int = 32
    max_wait_ms: float = 2.0  # batching window when the queue is empty


# ``buckets`` / ``_bucket_for`` moved to serving/aot.py with the AOT cache;
# re-exported here because the legacy loop and its tests import them from
# this module.
_bucket_for = bucket_for


def _random_state(cfg: TMConfig, rng: np.random.Generator,
                  include_density: float) -> TMState:
    """Random sparse include state — serving benchmarks measure evaluation,
    not training quality."""
    inc = rng.uniform(size=(cfg.n_classes, cfg.n_clauses,
                            cfg.n_literals)) < include_density
    return TMState(ta_state=jnp.asarray(
        np.where(inc, cfg.n_states + 1, cfg.n_states), jnp.int16))


def serve_engine(session: TMSession, bundle, x_all: np.ndarray,
                 arrivals: np.ndarray, *, engine: str,
                 policy: ServePolicy) -> dict:
    """Run the legacy closed-loop batched loop for one engine.

    The simulated clock advances by measured compute only, so the
    percentiles are clean per-batch compute under a synthetic load — but
    the throughput splices compute windows end-to-end and excludes every
    host-side gap; compare wall-clock claims through ``run_sustained``
    instead (DESIGN.md §10). ``compile_s_per_bucket`` keys are *strings*
    deliberately: the record is JSON, where int keys would be silently
    coerced — emitting them as strings keeps the in-memory record
    identical to a load of the written file (docs/BENCH_SCHEMAS.md).
    """
    sizes = buckets(policy.max_batch,
                    min_batch=session.topology.data_shards)
    o = x_all.shape[1]

    compile_s = {}
    for b in sizes:  # compile every bucket before the timed loop
        t0 = time.perf_counter()
        jax.block_until_ready(
            session.scores(bundle, jnp.zeros((b, o), jnp.uint8),
                           engine=engine))
        compile_s[str(b)] = round(time.perf_counter() - t0, 4)

    n = x_all.shape[0]
    wait = policy.max_wait_ms / 1e3
    clock = float(arrivals[0])
    i = 0
    lat: list[float] = []
    rows_real = rows_padded = n_batches = 0
    cap = sizes[-1]  # top bucket (≤ max_batch, multiple of the data shards)
    while i < n:
        if arrivals[i] > clock:               # idle: admit next + hold window
            clock = float(arrivals[i]) + wait
        k = int(np.searchsorted(arrivals[i:i + cap], clock, side="right"))
        k = max(k, 1)
        b = _bucket_for(k, sizes)
        xp = np.zeros((b, o), np.uint8)
        xp[:k] = x_all[i:i + k]
        t0 = time.perf_counter()
        jax.block_until_ready(
            session.scores(bundle, jnp.asarray(xp), engine=engine))
        done = clock + (time.perf_counter() - t0)
        lat.extend(done - arrivals[i:i + k])
        rows_real += k
        rows_padded += b
        n_batches += 1
        clock = done
        i += k

    lat_ms = np.asarray(lat) * 1e3
    p50, p90, p95, p99 = np.percentile(lat_ms, [50, 90, 95, 99])
    throughput = n / (clock - float(arrivals[0]))
    offered = n / (float(arrivals[-1]) - float(arrivals[0]) + 1e-12)
    # Saturated: the engine drains slower than requests arrive, so the queue
    # grows for the whole run and the percentiles measure backlog (they scale
    # with n_requests), not serving latency. Flagged so cross-PR tracking
    # never compares a backlog artifact against a real tail latency.
    saturated = throughput < 0.95 * offered
    return {
        "engine": engine,
        "saturated": bool(saturated),
        "requests": n,
        "batches": n_batches,
        "mean_batch": round(rows_real / n_batches, 2),
        "padding_efficiency": round(rows_real / rows_padded, 4),
        "latency_ms": {"p50": round(float(p50), 3),
                       "p90": round(float(p90), 3),
                       "p95": round(float(p95), 3),
                       "p99": round(float(p99), 3),
                       "mean": round(float(lat_ms.mean()), 3),
                       "max": round(float(lat_ms.max()), 3)},
        "throughput_rps": round(throughput, 1),
        "compile_s_per_bucket": compile_s,
    }


def run(cfg: TMConfig, *, engines=("indexed",), topology: Topology | None = None,
        n_requests: int = 512, rps: float = 2000.0,
        policy: ServePolicy = ServePolicy(), seed: int = 0,
        include_density: float = 0.08) -> dict:
    """Serve a synthetic load through each engine on one topology."""
    rng = np.random.default_rng(seed)
    session = TMSession(cfg, topology, engines=engines)
    bundle = session.prepare(_random_state(cfg, rng, include_density))

    x_all, _ = binarized_images(n_requests, cfg.n_features, cfg.n_classes,
                                seed=seed + 1)
    arrivals = np.cumsum(rng.exponential(1.0 / rps, n_requests))

    record = {
        "config": {"n_classes": cfg.n_classes, "n_clauses": cfg.n_clauses,
                   "n_features": cfg.n_features},
        "load": {"requests": n_requests, "rps": rps},
        "policy": {"max_batch": policy.max_batch,
                   "max_wait_ms": policy.max_wait_ms},
        "devices": jax.local_device_count(),
        "topology": session.describe(),
        "engines": {},
    }
    for engine in engines:
        record["engines"][engine] = serve_engine(
            session, bundle, x_all, arrivals, engine=engine, policy=policy)
    return record


def _saturation_rps(server, xs: np.ndarray, *, step_duration_s: float,
                    rng: np.random.Generator,
                    start_rps: float = 250.0) -> tuple[float, list[dict]]:
    """Ramp offered load ×4 until the server stops holding it.

    An overloaded open-loop step keeps the server continuously busy, so the
    achieved
    rate of the first non-holding step *is* the server's capacity; the max
    achieved across the ramp is returned to absorb step noise.
    """
    steps, rate = [], start_rps
    while rate <= 4e6:
        step = run_step(server, xs, rps=rate, duration_s=step_duration_s,
                        rng=rng)
        steps.append(step)
        if not holds(step):
            break
        rate *= 4
    return max(s["achieved_rps"] for s in steps), steps


# offered-rate ladder for the async sweep, as multiples of the measured
# sync baseline — dense around 1.0 so the knee resolves whether the async
# runtime clears the baseline, with overload steps past it
ASYNC_LADDER = (0.4, 0.8, 1.05, 1.3, 1.8, 2.6)


def run_sustained(cfg: TMConfig, *, engines=("indexed",),
                  topology: Topology | None = None, max_batch: int = 32,
                  step_duration_s: float = 1.0, seed: int = 0,
                  include_density: float = 0.08) -> dict:
    """The open-loop sync-vs-async comparison (``sustained_load`` section
    of the schema-2 record).

    Per engine: a ``SyncTMServer`` — the old blocking drain loop behind
    the modern submit surface — is ramped to saturation, then an
    ``AsyncTMServer`` over a shared AOT bucket cache sweeps an offered
    ladder scaled to that baseline. Both modes run through the *same*
    Poisson load generator on the same wall clock, so
    ``knee_exceeds_sync`` is a fair claim (unlike the legacy
    ``serve_engine`` throughput, whose simulated clock splices compute
    windows — DESIGN.md §10).
    """
    rng = np.random.default_rng(seed)
    session = TMSession(cfg, topology, engines=engines)
    bundle = session.prepare(_random_state(cfg, rng, include_density))
    xs, _ = binarized_images(512, cfg.n_features, cfg.n_classes,
                             seed=seed + 1)
    aot = AOTBucketCache(session, bundle, engines=tuple(engines),
                         max_batch=max_batch)
    out = {"step_duration_s": step_duration_s,
           "ladder": list(ASYNC_LADDER), "engines": {}}
    for engine in engines:
        sync = SyncTMServer(session, bundle, engine=engine,
                            max_batch=max_batch).start()
        base, ramp = _saturation_rps(
            sync, xs, step_duration_s=step_duration_s,
            rng=np.random.default_rng(seed + 2))
        sync.stop()

        server = AsyncTMServer(session, bundle, engine=engine,
                               max_batch=max_batch, aot=aot).start()
        rec = sustained_load(server, xs,
                             rps_steps=[m * base for m in ASYNC_LADDER],
                             step_duration_s=step_duration_s,
                             seed=seed + 3)
        server.stop()

        rec["sync_baseline"] = {
            "achieved_rps": base,
            "ramp": [{"offered_rps": s["offered_rps"],
                      "achieved_rps": s["achieved_rps"],
                      "rejection_rate": s["rejection_rate"]}
                     for s in ramp]}
        rec["knee_exceeds_sync"] = bool(rec["knee"]["achieved_rps"] > base)
        rec["speedup_at_knee"] = (
            round(rec["knee"]["achieved_rps"] / base, 3) if base else None)
        out["engines"][engine] = rec
    out["compile_s_per_bucket"] = aot.compile_report()
    out["knee_exceeds_sync"] = all(
        r["knee_exceeds_sync"] for r in out["engines"].values())
    return out


def run_batch_axis_scaling(cfg: TMConfig, *, engine: str = "indexed",
                           device_counts=None, n_requests: int = 256,
                           rps: float = 2000.0,
                           policy: ServePolicy = ServePolicy(),
                           seed: int = 0, include_density: float = 0.08,
                           backend: str | None = None,
                           reuse: dict | None = None) -> list[dict]:
    """The same load at 1, 2, … data shards: batch-axis scaling per device
    count (the scores path is communication-free over ``data``, so this is
    the ROADMAP's multi-device ``tm_serve`` measurement).

    ``backend`` is the kernel backend of the *whole* sweep — it must match
    the caller's serving backend, or the per-device-count rows would mix
    kernel routes with incomparable magnitudes (interpret-mode Pallas vs
    compiled XLA). ``reuse`` maps a device count to an already-measured
    ``serve_engine`` record for the identical load *and backend* (e.g. the
    caller's main record), so that count is not benchmarked twice.
    """
    if device_counts is None:
        device_counts, d = [], 1
        while d <= min(jax.local_device_count(), policy.max_batch):
            device_counts.append(d)
            d *= 2
    out = []
    for d in device_counts:
        r = (reuse or {}).get(d)
        if r is None:
            rec = run(cfg, engines=(engine,),
                      topology=Topology(data_shards=d, backend=backend),
                      n_requests=n_requests, rps=rps, policy=policy,
                      seed=seed, include_density=include_density)
            r = rec["engines"][engine]
        out.append({"devices": d, "data_shards": d, "engine": engine,
                    "throughput_rps": r["throughput_rps"],
                    "p50_ms": r["latency_ms"]["p50"],
                    "p95_ms": r["latency_ms"]["p95"],
                    "saturated": r["saturated"]})
    return out


# --smoke supplies these as *defaults* — any explicitly-passed flag wins
# (bitpack in the smoke engine set resolves through the kernel backend
# registry, so CI's --backend pallas_interpret exercises that route)
SMOKE_DEFAULTS = {"engine": "indexed,bitpack", "classes": 4, "clauses": 64,
                  "features": 48, "requests": 96, "max_batch": 8,
                  "step_duration": 0.3}
FULL_DEFAULTS = {"engine": "indexed", "classes": 10, "clauses": 256,
                 "features": 196, "requests": 512, "max_batch": 32,
                 "step_duration": 1.0}


def resolve_flags(smoke: bool, **flags) -> dict:
    """Merge CLI flags with the mode's defaults.

    ``--smoke`` selects a *default set*, never an override: a flag the
    user passed explicitly (non-None) always wins. The old CLI silently
    discarded explicit ``--requests``/``--max-batch``/``--classes``/
    ``--clauses``/``--features`` whenever ``--smoke`` was set.
    """
    base = SMOKE_DEFAULTS if smoke else FULL_DEFAULTS
    unknown = set(flags) - set(base)
    if unknown:
        raise ValueError(f"unknown flags {sorted(unknown)}; "
                         f"resolvable: {sorted(base)}")
    return {k: (base[k] if v is None else v) for k, v in flags.items()}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description="batched TM serving benchmark")
    ap.add_argument("--engine", default=None,
                    help="comma-separated registry engine names")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rps", type=float, default=2000.0)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--classes", type=int, default=None)
    ap.add_argument("--clauses", type=int, default=None)
    ap.add_argument("--features", type=int, default=None)
    ap.add_argument("--step-duration", type=float, default=None,
                    help="seconds per open-loop load step (sustained_load)")
    ap.add_argument("--data-shards", type=int, default=None,
                    help="serve data-sharded over this many devices "
                         "(default: all available)")
    ap.add_argument("--clause-shards", type=int, default=1)
    from repro.kernels.backend import BACKENDS
    ap.add_argument("--backend", default=None, choices=list(BACKENDS),
                    help="kernel backend the TM primitives resolve through "
                         "(kernels/backend.py; default: TMConfig's 'auto')")
    ap.add_argument("--no-scaling", action="store_true",
                    help="skip the per-device-count batch-axis sweep")
    ap.add_argument("--no-sustained", action="store_true",
                    help="skip the open-loop sync-vs-async sustained_load "
                         "sweep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_tm_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny defaults for CI (scripts/ci.sh); explicit "
                         "flags still win")
    args = ap.parse_args()

    n_dev = jax.local_device_count()
    r = resolve_flags(args.smoke, engine=args.engine, classes=args.classes,
                      clauses=args.clauses, features=args.features,
                      requests=args.requests, max_batch=args.max_batch,
                      step_duration=args.step_duration)
    cfg = TMConfig(n_classes=r["classes"], n_clauses=r["clauses"],
                   n_features=r["features"])
    engines = tuple(r["engine"].split(","))
    n_requests, max_batch = r["requests"], r["max_batch"]
    for e in engines:
        if e not in registered_engines():
            raise SystemExit(f"unknown engine {e!r}; "
                             f"registered: {registered_engines()}")

    # default placement: spread spare devices over data, but never beyond
    # max_batch (batches must divide over the data axis — buckets() errors
    # on an explicit --data-shards that violates this)
    data_shards = (args.data_shards if args.data_shards is not None
                   else min(max(n_dev // args.clause_shards, 1), max_batch))
    topology = Topology(data_shards=data_shards,
                        clause_shards=args.clause_shards,
                        backend=args.backend)
    policy = ServePolicy(max_batch=max_batch, max_wait_ms=args.max_wait_ms)
    record = run(cfg, engines=engines, topology=topology,
                 n_requests=n_requests, rps=args.rps, policy=policy,
                 seed=args.seed)
    record["schema"] = 2
    if not args.no_sustained:
        record["sustained_load"] = run_sustained(
            cfg, engines=engines, topology=topology, max_batch=max_batch,
            step_duration_s=r["step_duration"], seed=args.seed)
    if not args.no_scaling and n_dev > 1:
        sweep_requests = (min(n_requests, 256) if not args.smoke
                          else n_requests)
        # the main record already measured this exact point — don't redo it
        reuse = ({data_shards: record["engines"][engines[0]]}
                 if args.clause_shards == 1 and sweep_requests == n_requests
                 else None)
        record["batch_axis_scaling"] = run_batch_axis_scaling(
            cfg, engine=engines[0], n_requests=sweep_requests,
            rps=args.rps, policy=policy, seed=args.seed,
            backend=args.backend, reuse=reuse)

    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    topo = record["topology"]
    print(f"topology: {topo['data_shards']}×data · {topo['clause_shards']}"
          f"×clause on {record['devices']} devices "
          f"({'sharded' if topo['sharded'] else 'single-device'} scores "
          f"path, backend={topo['backend']}, "
          f"composition={topo['composition']})")
    for name, r in record["engines"].items():
        lm = r["latency_ms"]
        tag = "  [SATURATED: offered load > capacity; percentiles are " \
              "backlog, lower --rps]" if r["saturated"] else ""
        print(f"{name}: p50={lm['p50']}ms p95={lm['p95']}ms "
              f"p99={lm['p99']}ms thru={r['throughput_rps']}req/s "
              f"pad_eff={r['padding_efficiency']}{tag}")
    for name, s in record.get("sustained_load", {}).get("engines",
                                                        {}).items():
        knee = s["knee"]
        print(f"sustained[{name}]: sync={s['sync_baseline']['achieved_rps']}"
              f"req/s · async knee={knee['achieved_rps']}req/s at offered "
              f"{knee['offered_rps']} ({s['speedup_at_knee']}x sync, "
              f"exceeds={s['knee_exceeds_sync']}, hot-loop compiles="
              f"{s['aot']['hot_loop_compiles']})")
    for row in record.get("batch_axis_scaling", []):
        print(f"scaling[{row['engine']}] devices={row['devices']}: "
              f"thru={row['throughput_rps']}req/s p95={row['p95_ms']}ms")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
