"""Paper Tables 1–3 analogue: indexed vs exhaustive TM throughput.

Grid: (dataset-family × features × clauses), measuring
  * inference us/sample for every requested registry engine
    (default: dense | bitpack_xla | compact | indexed — ``bitpack_xla``
    is the backend-registry alias pinning the packed engine to the XLA
    body, so the grid times identically on every host; the
    ``backend_topology_sweep`` below covers the kernel routes),
  * training us/sample for dense learning with / without engine-cache
    maintenance (the jit-native ``api.train_step``),
  * the §3 'Remarks' WORK RATIO (indexed literal-inspections / dense),
    which is hardware-independent — the paper's 0.02 (MNIST) / 0.006 (IMDb)
    claims are validated here exactly.

Engine caches are prepared through the registry with *static* capacities
derived from the config (``index_capacity`` / ``clause_capacity`` at a 4×
expected-length capacity factor, cf. MoE expert capacity) — there is no
data-dependent host sync anywhere on the timed paths.

``run()`` returns machine-readable rows; ``main`` writes them to
``BENCH_tm.json`` so the perf trajectory is tracked across PRs.

Container scaling: sample counts and the clause grid are scaled down for
the 1-core CPU (the paper used full datasets on a desktop CPU); trends —
speedup grows with clause count, IMDb training slows down under index
maintenance — are the reproduction target, magnitudes are host-specific.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tm import fmnist_like, imdb_like, mnist_like
from repro.core import api, indexing, tm
from repro.core.engines import get_engine
from repro.core.types import TMConfig, TMState
from repro.data.synthetic import binarized_images, bow_documents

DEFAULT_ENGINES = ("dense", "bitpack_xla", "compact", "indexed")


def _timeit(fn, *args, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def synthetic_trained_state(cfg: TMConfig, avg_clause_len: float, seed=0):
    """TM state with paper-matched clause sparsity (include prob =
    avg_len / 2o), standing in for a trained machine's sparsity profile."""
    rng = np.random.default_rng(seed)
    p = avg_clause_len / cfg.n_literals
    inc = rng.uniform(size=(cfg.n_classes, cfg.n_clauses,
                            cfg.n_literals)) < p
    ta = np.where(inc, cfg.n_states + 1, cfg.n_states).astype(np.int16)
    return TMState(ta_state=jnp.asarray(ta))


def work_ratio(cfg: TMConfig, state: TMState, xs) -> float:
    """Paper §3 Remarks: (Σ_{k false} |L_k|) / (n·2o) per class-eval."""
    idx = indexing.build_index(cfg, state, cfg.resolved_index_capacity)
    w = np.asarray(indexing.indexed_work(idx, xs)).mean()
    return float(w) / indexing.dense_work(cfg)


def bench_cell(exp, n_clauses: int, *, engines=DEFAULT_ENGINES,
               n_eval=32, n_train=16, seed=0):
    # static cache capacities: 4× the expected list/clause length (cf. MoE
    # capacity factor); worst-case capacity makes the scatter/gather paths
    # do n/len× more masked work (§Perf hillclimb C)
    cap = min(n_clauses,
              max(16, int(4 * n_clauses * exp.avg_clause_len
                          / exp.tm.n_literals)))
    l_max = min(exp.tm.n_literals, max(16, int(4 * exp.avg_clause_len)))
    cfg = dataclasses.replace(exp.tm, n_clauses=n_clauses,
                              index_capacity=cap, clause_capacity=l_max)
    if exp.dataset == "image":
        xs, ys = binarized_images(n_eval + n_train, cfg.n_features,
                                  cfg.n_classes, seed=seed)
    else:
        xs, ys = bow_documents(n_eval + n_train, cfg.n_features,
                               cfg.n_classes, seed=seed)
    xs = jnp.asarray(xs)
    ys = jnp.asarray(ys)
    x_eval = xs[:n_eval]
    x_tr, y_tr = xs[n_eval:], ys[n_eval:]

    state = synthetic_trained_state(cfg, exp.avg_clause_len, seed)

    r: dict = {"family": exp.name, "features": cfg.n_features,
               "clauses": n_clauses, "engines": list(engines)}
    r["work_ratio"] = work_ratio(cfg, state, x_eval)

    # inference engines via the registry — caches prepared once (as during
    # learning), passed as jit ARGS (a closure constant triggers multi-second
    # XLA constant folding of the packed tables and pollutes logs)
    for name in engines:
        eng = get_engine(name)
        cache = jax.jit(lambda s, e=eng: e.prepare(cfg, s))(state)
        fn = jax.jit(lambda c, x, e=eng: e.scores(cfg, c, x))
        # every engine times the full eval batch — the matmul-form indexed
        # path removed the old 2-sample truncation (no residual cap)
        r[f"infer_{name}_us"] = _timeit(fn, cache, x_eval) / n_eval * 1e6
    if "dense" in engines:
        for name in engines:
            if name != "dense":
                r[f"infer_speedup_{name}"] = (r["infer_dense_us"]
                                              / r[f"infer_{name}_us"])

    # training: dense learning alone vs the full jit-native train_step
    # (feedback + event diff + incremental cache maintenance for the paper's
    # index — O(1) *work* per boundary crossing; wall-time constant factors
    # of the functional scatter path are runtime-specific, see EXPERIMENTS.md)
    key = jax.random.key(seed)
    plain = jax.jit(
        lambda s, x, y: tm.update_batch_sequential(cfg, s, x, y, key))
    t_plain = _timeit(plain, state, x_tr, y_tr, reps=1)

    bundle = api.init_bundle(cfg, engines=("indexed",), state=state)
    step = jax.jit(lambda b, x, y: api.train_step(b, x, y, key,
                                                  max_events=512))
    t_idx = _timeit(step, bundle, x_tr, y_tr, reps=1)
    r["train_plain_us"] = t_plain / n_train * 1e6
    r["train_indexed_us"] = t_idx / n_train * 1e6
    r["train_speedup"] = t_plain / t_idx
    return r


GRID_FAMILIES = [mnist_like, fmnist_like]
CLAUSE_GRID = (256, 1024, 4096)


# ---------------------------------------------------------------------------
# Engine × backend × topology sweep (kernel backend registry, DESIGN.md §8)
# ---------------------------------------------------------------------------


def backend_topology_sweep(*, engines=("bitpack", "indexed"),
                           backends=None, n_eval=32, n_train=8,
                           seed=0) -> list[dict]:
    """Inference + train-step timings per (engine × backend × topology).

    Backends come from the kernel registry (``kernels/backend.py``):
    ``xla`` and ``pallas_interpret`` everywhere, plus compiled ``pallas``
    when this host is a TPU. Topologies: single-device always, plus — when
    the host exposes ≥ 4 devices (CI forces 4 via
    ``--xla_force_host_platform_device_count``) — a 4-way clause-sharded
    placement and a **ragged** 2×2 data×clause placement on a smaller
    clause count whose per-shard slice does not divide by the data ranks
    (``composition='composed_ragged'``, DESIGN.md §9), so the composed
    hierarchical route is timed alongside the even ones. Every row records
    its ``data_shards`` and the fired ``composition`` rule. Interpret-mode
    rows measure the *route* (they execute the kernel body in Python, so
    their magnitudes are not comparable to compiled rows — recorded for
    completeness, compared only like-for-like across PRs).
    """
    from repro.core.session import TMSession, Topology
    from repro.kernels import backend as kbackend

    if backends is None:
        backends = ("xla", "pallas_interpret")
        if jax.default_backend() == "tpu":
            backends += ("pallas",)
    cfg0 = TMConfig(n_classes=10, n_clauses=256, n_features=196)
    # clause_shards=2 → n_local=65; data_shards=2 does not divide it →
    # the previously-replicated shape that now composes raggedly
    cfg_ragged = dataclasses.replace(cfg0, n_clauses=130)
    topo_grid = [(cfg0, Topology())]
    if jax.local_device_count() >= 4:
        topo_grid.append((cfg0, Topology(clause_shards=4)))
        topo_grid.append((cfg_ragged, Topology(clause_shards=2,
                                               data_shards=2)))

    states = {
        c.n_clauses: synthetic_trained_state(
            dataclasses.replace(c, backend="xla"), 58.0, seed)
        for c, _ in topo_grid}
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.integers(0, 2, (n_eval, cfg0.n_features)), jnp.uint8)
    txs = jnp.asarray(rng.integers(0, 2, (n_train, cfg0.n_features)),
                      jnp.uint8)
    tys = jnp.asarray(rng.integers(0, cfg0.n_classes, n_train), jnp.int32)
    key = jax.random.key(seed)

    rows = []
    for backend in backends:
        for cfg_base, topo in topo_grid:
            cfg = dataclasses.replace(cfg_base, backend=backend)
            for engine in engines:
                # donate=False: the timing loop reuses one bundle across reps
                session = TMSession(
                    cfg, dataclasses.replace(topo, engines=(engine,),
                                             donate=False))
                bundle = session.prepare(states[cfg.n_clauses])
                fn = lambda b, x: session.scores(b, x, engine=engine)
                t_inf = _timeit(fn, bundle, xs)
                t_tr = _timeit(
                    lambda b, x, y: session.train_step(b, x, y, key),
                    bundle, txs, tys, reps=1)
                rows.append({
                    "engine": engine,
                    "backend": kbackend.resolve_backend(backend),
                    "n_clauses": cfg.n_clauses,
                    "clause_shards": topo.clause_shards,
                    "data_shards": topo.data_shards,
                    "composition": session.describe()["composition"],
                    "devices": jax.local_device_count(),
                    "infer_us": t_inf / n_eval * 1e6,
                    "train_us": t_tr / n_train * 1e6,
                })
    return rows


# ---------------------------------------------------------------------------
# Indexed vs dense speedup curve (the paper's headline claim, schema 4)
# ---------------------------------------------------------------------------


def indexed_speedup_curve(*, clause_grid=(64, 256), avg_lens=(8.0, 58.0),
                          n_features=196, n_eval=32, seed=0) -> list[dict]:
    """Indexed-vs-dense inference over (n_clauses × clause sparsity).

    The paper's Tables 1–2 trend in miniature: speedup grows with clause
    count and with sparsity (short clauses → tiny work ratio). Both engines
    time the *full* eval batch through the registry on the ``xla`` backend
    (the indexed route is the matmul-form Eq. 4 body); ``work_ratio`` is
    the hardware-independent §3 Remarks quantity recorded next to the
    measured wall-clock ratio. CI gates the sparsest high-clause cell:
    indexed must strictly beat dense there.
    """
    rows = []
    for n_c in clause_grid:
        for avg_len in avg_lens:
            cfg = TMConfig(n_classes=10, n_clauses=n_c,
                           n_features=n_features, backend="xla",
                           index_capacity=n_c)
            state = synthetic_trained_state(cfg, avg_len, seed)
            rng = np.random.default_rng(seed)
            xs = jnp.asarray(rng.integers(0, 2, (n_eval, n_features)),
                             jnp.uint8)
            row = {"n_clauses": n_c, "avg_clause_len": avg_len,
                   "features": n_features,
                   "work_ratio": work_ratio(cfg, state, xs)}
            for name in ("dense", "indexed"):
                eng = get_engine(name)
                cache = jax.jit(lambda s, e=eng: e.prepare(cfg, s))(state)
                fn = jax.jit(lambda c, x, e=eng: e.scores(cfg, c, x))
                row[f"infer_{name}_us"] = _timeit(fn, cache, xs) / n_eval * 1e6
            row["speedup"] = row["infer_dense_us"] / row["infer_indexed_us"]
            rows.append(row)
    return rows


def print_indexed_speedup(rows: list[dict]) -> None:
    """One line per indexed-speedup cell (shared with benchmarks/run.py)."""
    for r in rows:
        print(f"indexed_speedup/n{r['n_clauses']}/len{r['avg_clause_len']:g}:"
              f" dense={r['infer_dense_us']:.2f}us"
              f" indexed={r['infer_indexed_us']:.2f}us"
              f" speedup={r['speedup']:.2f}x work={r['work_ratio']:.4f}")


# ---------------------------------------------------------------------------
# Sync vs async stale-vote training sweep (DESIGN.md §11)
# ---------------------------------------------------------------------------


def train_sync_vs_async(*, ks=(0, 1, 4, 16), shard_grid=(2, 4), batch=32,
                        steps_timed=16, steps_train=48, n_eval=256,
                        seed=0) -> list[dict]:
    """Sequential train-step time + accuracy per (async_votes K × shards).

    K=0 is today's synchronous path (one vote psum per class round inside
    the batch scan + a per-step overflow psum); K>0 trains against the
    K-step-stale vote sum with the refresh all-reduce amortised into the
    timed window — so ``speedup_vs_sync`` is exactly the removed-collective
    win. Every row also trains a fresh machine on the same synthetic
    binarized-image stream and records its held-out ``accuracy`` next to
    the K=0 row's (``accuracy_delta``) — the parity the async mode must
    hold (the gate itself lives in tests/test_tm_async.py; this records
    the magnitudes). Empty on hosts with fewer devices than
    ``max(shard_grid)`` (CI forces 4).
    """
    from repro.core.session import TMSession, Topology
    from repro.core.types import init_tm

    if jax.local_device_count() < max(shard_grid):
        return []
    cfg = TMConfig(n_classes=10, n_clauses=128, n_features=196,
                   backend="xla")
    xs, ys = binarized_images(batch * steps_train + n_eval, cfg.n_features,
                              cfg.n_classes, seed=seed)
    xs, ys = jnp.asarray(xs), jnp.asarray(ys)
    x_ev, y_ev = xs[:n_eval], ys[:n_eval]
    xt, yt = xs[n_eval:], ys[n_eval:]

    rows = []
    for shards in shard_grid:
        sync_row = None
        for k in ks:
            session = TMSession(
                cfg, Topology(clause_shards=shards, async_votes=k,
                              engines=("dense",), donate=False))
            bundle = session.prepare(init_tm(cfg))
            key = jax.random.key(seed)
            for i in range(steps_train):  # accuracy + executable warmup
                key, sub = jax.random.split(key)
                b0 = i * batch
                bundle = session.train_step(
                    bundle, xt[b0:b0 + batch], yt[b0:b0 + batch], sub)
            bundle = session.refresh_votes(bundle)
            acc = float(jnp.mean(
                (session.predict(bundle, x_ev, engine="dense")
                 == y_ev).astype(jnp.float32)))
            jax.block_until_ready(bundle.state.ta_state)
            t0 = time.perf_counter()
            for i in range(steps_timed):  # amortises the K-step refreshes
                key, sub = jax.random.split(key)
                b0 = (i % steps_train) * batch
                bundle = session.train_step(
                    bundle, xt[b0:b0 + batch], yt[b0:b0 + batch], sub)
            jax.block_until_ready(bundle.state.ta_state)
            step_us = (time.perf_counter() - t0) / steps_timed * 1e6
            row = {"k": k, "clause_shards": shards,
                   "data_shards": 1,
                   "composition": session.describe()["composition"],
                   "devices": jax.local_device_count(),
                   "batch": batch, "step_us": step_us, "accuracy": acc}
            if k == 0:
                sync_row = row
            row["accuracy_sync"] = sync_row["accuracy"]
            row["accuracy_delta"] = acc - sync_row["accuracy"]
            row["speedup_vs_sync"] = sync_row["step_us"] / step_us
            rows.append(row)
    return rows


def print_sync_vs_async(rows: list[dict]) -> None:
    """One line per sync-vs-async row (shared with benchmarks/run.py)."""
    for r in rows:
        print(f"sync_vs_async/c{r['clause_shards']}/K={r['k']}"
              f"[{r['composition']}]: step={r['step_us']:.0f}us "
              f"speedup={r['speedup_vs_sync']:.2f}x "
              f"acc={r['accuracy']:.3f} (Δ{r['accuracy_delta']:+.3f})")


def run(fast: bool = True, engines=DEFAULT_ENGINES):
    rows = []
    clause_grid = CLAUSE_GRID[:2] if fast else CLAUSE_GRID
    for fam in GRID_FAMILIES:
        for bits in ((1, 2) if fast else (1, 2, 3, 4)):
            for n_c in clause_grid:
                rows.append(bench_cell(fam(bits), n_c, engines=engines))
    for o in ((5000,) if fast else (5000, 10000, 20000)):
        for n_c in clause_grid:
            rows.append(bench_cell(imdb_like(o), n_c, engines=engines))
    return rows


def print_sweep(sweep: list[dict], prefix: str = "sweep") -> None:
    """One line per backend-sweep row (shared by main and benchmarks/run.py)."""
    for r in sweep:
        print(f"{prefix}/{r['engine']}/{r['backend']}"
              f"/c{r['clause_shards']}xd{r['data_shards']}"
              f"[{r['composition']}]: "
              f"infer={r['infer_us']:.2f}us train={r['train_us']:.2f}us")


def write_json(rows, path: str = "BENCH_tm.json",
               backend_sweep=None, train_sync_vs_async=None,
               indexed_speedup=None) -> None:
    """Machine-readable perf record, one file per run (tracked across PRs)."""
    payload = {
        "bench": "tm_speedup",
        "schema": 4,
        "backend": jax.default_backend(),
        "host": platform.machine(),
        "devices": jax.local_device_count(),
        "units": {"infer_*_us": "us/sample", "train_*_us": "us/sample",
                  "step_us": "us/step",
                  "work_ratio": "indexed/dense literal inspections"},
        "rows": rows,
        "backend_sweep": backend_sweep or [],
        "train_sync_vs_async": train_sync_vs_async or [],
        "indexed_speedup": indexed_speedup or [],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--engines", default=",".join(DEFAULT_ENGINES))
    ap.add_argument("--out", default="BENCH_tm.json",
                    help="JSON output path ('' to skip)")
    ap.add_argument("--sweep-only", action="store_true",
                    help="run only the engine×backend×topology sweep "
                         "(the CI gate on a forced multi-device host)")
    args = ap.parse_args()
    engines = tuple(args.engines.split(","))

    if args.sweep_only:
        sweep = backend_topology_sweep()
        print_sweep(sweep)
        curve = indexed_speedup_curve()
        print_indexed_speedup(curve)
        sva = train_sync_vs_async()
        print_sync_vs_async(sva)
        if args.out:
            write_json([], args.out, backend_sweep=sweep,
                       train_sync_vs_async=sva, indexed_speedup=curve)
        return

    rows = run(fast=not args.full, engines=engines)
    cols = ["family", "features", "clauses", "work_ratio"]
    cols += [f"infer_{e}_us" for e in engines]
    if "dense" in engines:  # speedups are only defined against the baseline
        cols += [f"infer_speedup_{e}" for e in engines if e != "dense"]
    cols += ["train_plain_us", "train_indexed_us", "train_speedup"]
    print(",".join(cols))
    for r in rows:
        print(",".join(
            f"{r[c]:.4g}" if isinstance(r[c], float) else str(r[c])
            for c in cols))
    sweep = backend_topology_sweep()
    print_sweep(sweep)
    curve = indexed_speedup_curve()
    print_indexed_speedup(curve)
    sva = train_sync_vs_async()
    print_sync_vs_async(sva)
    if args.out:
        write_json(rows, args.out, backend_sweep=sweep,
                   train_sync_vs_async=sva, indexed_speedup=curve)


if __name__ == "__main__":
    main()
