"""Chip smoke test: the TM train-and-serve path on a TPU at MNIST width.

Drives the paper's main path once, through the entry points a user calls,
at the width of ``configs/tm.py`` ``mnist_like(1)`` (10 classes, 2000
clauses per class, 784 binarized features = 1568 literals), with random
data made from a seed:

* train: a few sequential ``TsetlinMachine.partial_fit`` steps on a
  ``binarized_images`` stream, once with ``backend="auto"`` (compiled Pallas
  on a TPU) and once with ``backend="xla"``. The two TA states must agree
  bit for bit, and no cache-sync event may overflow.
* serve: a few dozen requests through ``AsyncTMServer`` and its AOT bucket
  cache, for the ``indexed`` and ``bitpack`` engines on both backends. Every
  answer must equal the XLA route's and the dense reference scores
  (``repro.core.scores``), and the AOT cache may not miss.
* route: the resolved backend is ``pallas`` and every lowered train and
  scores step holds a ``tpu_custom_call`` (and the XLA route none).
* rows: the learning round's ``clause_outputs_rows`` kernel against its
  XLA body on seeded TA rows at the M1, I1 and I4-shard widths (2000 ×
  1568, 2000 × 10,000, 5000 × 40,000), whose trailing literal tile is
  partial at I1 and I4: lanes the kernel failed to mask would read
  whatever the chip's memory holds there, which interpret mode cannot show.

``--chips 4`` runs only the sharded phase instead: a few steps and scores
on ``Topology(clause_shards=4)`` and ``Topology(data_shards=2,
clause_shards=2)``, each compared bit for bit with ``Topology(1)`` in the
same process, with the sharded bundle's arrays spanning four devices.

Earlier lines report phase times (compilation counts as set-up), the device
kind and peak device memory. The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no result. One process drives every chip; nothing is spawned.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded phase, four chips
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
TRAIN_BATCH = 2      # samples per sequential train step
TRAIN_STEPS = 3
N_REQUESTS = 48      # served rows (one chip); a multiple of every data axis
N_SHARDED_ROWS = 16  # scored rows per topology (four chips)
MAX_BATCH = 8        # top AOT padding bucket
ENGINES = ("indexed", "bitpack")
ROW_WIDTHS = {"M1": (2000, 1568), "I1": (2000, 10000),
              "I4 shard": (5000, 40000)}   # (clauses, literals)


def check(cond: bool, msg: str) -> None:
    """Raise (never assert: ``python -O`` must not skip a check)."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def log(msg: str) -> None:
    print(msg, flush=True)


def max_events(cfg, batch: int) -> int:
    """Event buffer sized to the worst load of one sequential step: each
    sample runs two class rounds, and a round may flip every TA of its
    class row — so overflow is impossible, not merely unlikely."""
    return min(cfg.n_classes, 2 * batch) * cfg.n_clauses * cfg.n_literals


def make_data(cfg, n_query: int):
    """Seeded ``binarized_images`` stream: train rows, then query rows."""
    import numpy as np

    from repro.data.synthetic import binarized_images
    n_train = TRAIN_STEPS * TRAIN_BATCH
    xs, ys = binarized_images(n_train + n_query, cfg.n_features,
                              cfg.n_classes, seed=SEED)
    return xs[:n_train], ys[:n_train], np.ascontiguousarray(xs[n_train:])


def train(cfg, xs, ys, topology=None):
    """Sequential ``partial_fit`` steps; the trainer maintains the bitpack
    cache only (serving sessions build the index from the trained state)."""
    from repro.core import Topology, TsetlinMachine
    topology = dataclasses.replace(topology or Topology(),
                                   engines=("bitpack",))
    machine = TsetlinMachine(
        cfg, topology=topology, seed=SEED,
        max_events_per_batch=max_events(cfg, TRAIN_BATCH)).init()
    for s in range(TRAIN_STEPS):
        rows = slice(s * TRAIN_BATCH, (s + 1) * TRAIN_BATCH)
        machine.partial_fit(xs[rows], ys[rows])
        check(machine.event_overflow == 0,
              f"step {s}: {machine.event_overflow} cache-sync events "
              "overflowed the event buffer")
    return machine


def lowered_train_step(machine, xs, ys) -> str:
    """StableHLO text of the machine's train step at this batch shape."""
    import jax

    from repro.core import train_step
    return jax.jit(train_step, static_argnames=("parallel", "max_events")
                   ).lower(machine.bundle, xs, ys, jax.random.key(SEED),
                           parallel=False,
                           max_events=machine.max_events_per_batch).as_text()


def serve(cfg, state, requests, engine: str):
    """Serve ``requests`` one row at a time through ``AsyncTMServer``;
    returns ``(scores (N, m), lowered top-bucket scores text, AOT set-up
    seconds — every bucket compiled and warmed)``."""
    import numpy as np

    from repro.core import TMSession, Topology
    from repro.serving import AsyncTMServer, ScoreResult
    session = TMSession(cfg, Topology(engines=(engine,)))
    bundle = session.prepare(state)
    t0 = time.perf_counter()
    server = AsyncTMServer(session, bundle, engine=engine,
                           max_batch=MAX_BATCH).start()
    compile_s = time.perf_counter() - t0
    promises = [server.submit(row, tenant=f"tenant{i % 3}")
                for i, row in enumerate(requests)]
    results = [p.wait(timeout=600) for p in promises]
    server.stop()
    check(all(isinstance(r, ScoreResult) for r in results),
          f"{engine}: a request was not served: "
          f"{[r for r in results if not isinstance(r, ScoreResult)][:1]}")
    aot = server.stats()["aot"]
    check(aot["misses"] == 0 and aot["lowerings"] == aot["entries"],
          f"{engine}: AOT cache missed or recompiled: {aot}")
    text = session.lower_scores(bundle, MAX_BATCH,
                                engine=engine).lowered.as_text()
    return np.stack([r.scores for r in results]), text, compile_s


def rows_parity(n_states: int, widths=ROW_WIDTHS, mode="pallas") -> None:
    """``clause_outputs_rows`` on the ``mode`` route against its XLA body,
    bit for bit, on seeded rows at each of ``widths``. Each clause includes
    about two literals, so outputs mix 0 and 1; row 0 includes only the
    last literal, which is false (output 0), and row 1 includes nothing
    (output 1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import backend as kbackend
    kernel = kbackend.resolve("clause_outputs_rows", mode)
    body = kbackend.resolve("clause_outputs_rows", "xla")
    key = jax.random.key(SEED)
    for name, (n, L) in widths.items():
        k_ta, k_inc, k_lit, key = jax.random.split(key, 4)
        ta = jax.random.randint(k_ta, (n, L), 1, n_states + 1, jnp.int16)
        inc = jax.random.uniform(k_inc, (n, L)) < 2.0 / L
        inc = inc.at[0].set(jnp.arange(L) == L - 1).at[1].set(False)
        ta = jnp.where(inc, ta + n_states, ta).astype(jnp.int16)
        lit = jax.random.bernoulli(k_lit, 0.5, (L,)).astype(jnp.uint8)
        lit = lit.at[L - 1].set(0)
        got = np.asarray(kernel(ta, lit, n_states=n_states))
        want = np.asarray(body(ta, lit, n_states=n_states))
        check(np.array_equal(got, want),
              f"clause_outputs_rows at {name} ({n} x {L}): "
              f"{int((got != want).sum())} clauses differ from the XLA body")
        check(list(want[:2]) == [0, 1] and 0 < want.mean() < 1,
              f"clause_outputs_rows at {name}: rows 0 and 1 read "
              f"{list(want[:2])}, mean {want.mean():.3f}")
        log(f"[rows] clause_outputs_rows at {name} ({n} x {L}): equal to "
            f"the XLA body; {int(want.sum())} of {n} clauses true")


def train_and_serve(cfg, backends=("auto", "xla")) -> dict:
    """The one-chip phase: train and serve on each backend, then hold every
    backend to the first's TA state and to the dense reference scores.
    Returns what the caller checks about the route."""
    import jax
    import numpy as np

    from repro.core import TMState, scores
    xs, ys, requests = make_data(cfg, N_REQUESTS)
    out = {}
    for backend in backends:
        c = dataclasses.replace(cfg, backend=backend)
        t0 = time.perf_counter()
        machine = train(c, xs, ys)
        ta = np.asarray(machine.state.ta_state)
        train_s = time.perf_counter() - t0
        served, texts, compile_s = {}, {}, 0.0
        t0 = time.perf_counter()
        for engine in ENGINES:
            served[engine], texts[engine], cs = serve(
                c, machine.state, requests, engine)
            compile_s += cs
        serve_s = time.perf_counter() - t0
        resolved = machine.session.describe()["backend"]
        log(f"[{backend}->{resolved}] train {TRAIN_STEPS}x{TRAIN_BATCH} "
            f"rows: {train_s:.3f} s (compile included); serve "
            f"{len(requests)} rows x {len(ENGINES)} engines: {serve_s:.3f} s "
            f"(AOT set-up {compile_s:.3f} s); includes "
            f"{int((ta > cfg.n_states).sum())}")
        out[backend] = {
            "resolved": resolved, "ta": ta, "served": served,
            "score_texts": texts,
            "train_text": lowered_train_step(machine, xs[:TRAIN_BATCH],
                                             ys[:TRAIN_BATCH]),
        }

    first = out[backends[0]]
    check((first["ta"] > cfg.n_states).any(), "training included nothing")
    ref = np.asarray(jax.jit(scores, static_argnums=0)(
        dataclasses.replace(cfg, backend="xla"),
        TMState(ta_state=jax.numpy.asarray(first["ta"])),
        requests))
    for backend in backends:
        check(np.array_equal(out[backend]["ta"], first["ta"]),
              f"TA state on {backend} differs from {backends[0]}")
        for engine in ENGINES:
            check(np.array_equal(out[backend]["served"][engine], ref),
                  f"{engine} scores on {backend} differ from the dense "
                  "reference")
    log(f"bit-exact: TA state and {len(ENGINES)} engines' scores agree "
        f"across {backends} and with the dense reference")
    return out


def sharded(cfg) -> None:
    """The four-chip phase: clause and data×clause topologies, each bit for
    bit against ``Topology(1)``, their bundles spanning four devices."""
    import jax
    import numpy as np

    from repro.core import TMSession, Topology
    xs, ys, query = make_data(cfg, N_SHARDED_ROWS)

    def serving_scores(topology, state):
        session = TMSession(cfg, dataclasses.replace(topology,
                                                     engines=ENGINES))
        bundle = session.prepare(state)
        return {e: np.asarray(session.scores(bundle, query, engine=e))
                for e in ENGINES}

    t0 = time.perf_counter()
    ref = train(cfg, xs, ys)
    ref_ta = np.asarray(ref.state.ta_state)
    ref_scores = serving_scores(Topology(), ref.state)
    log(f"[Topology(1)] train + score: {time.perf_counter() - t0:.3f} s")
    for topology in (Topology(clause_shards=4),
                     Topology(data_shards=2, clause_shards=2)):
        name = (f"Topology(data_shards={topology.data_shards}, "
                f"clause_shards={topology.clause_shards})")
        t0 = time.perf_counter()
        machine = train(cfg, xs, ys, topology)
        leaves = [x for x in jax.tree.leaves(machine.bundle)
                  if isinstance(x, jax.Array)]
        spans = {len(x.sharding.device_set) for x in leaves}
        check(spans == {4}, f"{name}: bundle arrays span {spans} devices")
        check(np.array_equal(np.asarray(machine.state.ta_state), ref_ta),
              f"{name}: TA state differs from Topology(1)")
        check(np.array_equal(np.asarray(machine.scores(query,
                                                       engine="bitpack")),
                             ref_scores["bitpack"]),
              f"{name}: trained bundle's bitpack scores differ")
        got = serving_scores(topology, machine.state)
        for e in ENGINES:
            check(np.array_equal(got[e], ref_scores[e]),
                  f"{name}: {e} scores differ from Topology(1)")
        log(f"[{name}] train + score: {time.perf_counter() - t0:.3f} s; "
            f"bit-exact with Topology(1); describe={machine.session.describe()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform!r} devices")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX found "
          f"{len(devices)}")

    from repro.configs.tm import mnist_like
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {devices[0].device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    cfg = mnist_like(1).tm
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded(cfg)
    else:
        rows_parity(cfg.n_states)
        out = train_and_serve(cfg)
        check(out["auto"]["resolved"] == "pallas",
              f"backend 'auto' resolved to {out['auto']['resolved']!r}")
        texts = {"train": out["auto"]["train_text"],
                 **out["auto"]["score_texts"]}
        for step, text in texts.items():
            check("tpu_custom_call" in text,
                  f"lowered {step} step holds no tpu_custom_call")
        xla_texts = [out["xla"]["train_text"],
                     *out["xla"]["score_texts"].values()]
        check(not any("tpu_custom_call" in t for t in xla_texts),
              "the xla route lowered a Pallas kernel")
        log(f"route: pallas kernels in {sorted(texts)}; none on xla")
    stats = devices[0].memory_stats() or {}
    log(f"total {time.perf_counter() - t0:.3f} s; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
