"""Online training of one TM clause-sharded over several chips.

The deployment: ``Topology(clause_shards=C)`` puts each class row's clauses
on ``C`` chips, ``n / C`` clauses a chip (at I4 with 20,000 clauses over
four chips, 5,000 clauses and 800 MB of int16 TA state per chip). Each
step runs ``distributed.make_sharded_train_step`` through
``TsetlinMachine.partial_fit``: every chip scans the whole batch over its
own clauses, with one vote all-reduce per class round, then selects and
replays its own events into its own bitpack words. The event buffer is
per shard and sized to the step's worst case, ``min(m, 2·batch)·(n /
C)·2o`` slots (400M at I4 / 20k), so no event can overflow.

Otherwise as ``train_online.py``: ``batch`` samples per step, drawn in a
seeded order from a pool of labelled samples and sent from the host each
step; set-up (counted in ``setup_s``) holds the pool, the TA state, the
machine and its ``warm_steps`` warm steps; the window steps the machine
until ``seconds`` have passed and ends on a blocked step, with at most
``depth`` steps in flight. The TA state is made from the seed directly in
the clause sharding (``sharded_state``), so no chip ever holds the whole
state.

Correct: the TA state after each warm step (copied to the host in set-up)
equals the plain reference's (``bench/ref.py``, unchanged) step from the
previous reference state, on the same samples and keys, and the reference
changes at least one TA cell in every warm step, so a run whose feedback
is switched off (every vote clipped at ±T) cannot pass. The reference runs
after the window, as in ``train_online.py``, on a state sharded over the
same chips and partitioned by the compiler: plain ``jax.numpy`` with no
``shard_map`` and no kernels, so its vote sum is the compiler's own
all-reduce. After the window the maintained bitpack words equal a fresh
pack of the final state, and no program is compiled or loaded in the
window.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench import harness, ref
from bench.gen import data, state


def sharded_state(tm: dict, proto: np.ndarray, avg_len: float, seed: int,
                  sharding):
    """``gen/state.make_state``'s TA state, bit for bit, made directly in
    ``sharding``: each chip computes its own clause rows (the draws are
    partitionable threefry, so every element keeps its value)."""
    import functools

    import jax
    import jax.numpy as jnp

    make = jax.jit(functools.partial(
        state.ta_state, n_clauses=tm["n_clauses"], n_states=tm["n_states"],
        avg_len=float(avg_len)), out_shardings=sharding)
    return make(state.jax_key(seed, 3), jnp.asarray(proto))


def run(cell: harness.Cell, seed: int, seconds: float,
        trace_dir: str | None, devices) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core import TMState, Topology, TsetlinMachine
    from repro.core.types import TMConfig

    tm, tr = cell.tm, cell.traffic
    m, n_cl, o = tm["n_classes"], tm["n_clauses"], tm["n_features"]
    batch, warm, shards = tr["batch"], tr["warm_steps"], tr["clause_shards"]
    compiles = harness.CompileCounter()

    xs, ys, proto = data.pool(cell.config["data"], m, o,
                              tr["pool_samples"], seed)
    order = data.rng_for(seed, 6).permutation(len(xs))

    def feed(step):
        idx = np.take(order, np.arange(step * batch, (step + 1) * batch),
                      mode="wrap")
        return xs[idx], ys[idx]

    base_key = state.jax_key(seed, 4)
    fold_in = jax.jit(jax.random.fold_in)
    n_local = -(-n_cl // shards)
    max_events = min(m, 2 * batch) * n_local * 2 * o
    machine = TsetlinMachine(
        TMConfig(**tm),
        topology=Topology(clause_shards=shards, engines=tuple(tr["engines"])),
        parallel=False, max_events_per_batch=max_events)
    mesh_devices = list(machine.session.mesh.devices.flat)
    if mesh_devices != list(devices):
        raise RuntimeError(f"the machine's mesh {mesh_devices} is not the "
                           f"devices given {list(devices)}")
    sharding = machine.session.state_sharding()
    avg_len = cell.config["state"]["avg_clause_len"]
    machine.bundle = machine.session.prepare(TMState(
        ta_state=sharded_state(tm, proto, avg_len, seed, sharding)))

    snapshots = []
    for step in range(warm):
        machine.partial_fit(*feed(step), rng=fold_in(base_key, step))
        snapshots.append(np.asarray(machine.bundle.state.ta_state))
        # the window copies each step's overflow counter: warm that program
        jnp.copy(machine.bundle.event_overflow).block_until_ready()
    jax.block_until_ready(machine.bundle)

    step = warm
    overflow = []
    compiles.active = True
    with harness.traced(trace_dir):
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            window_start = time.monotonic()
            while True:
                with TraceAnnotation("bench.step"):
                    machine.partial_fit(*feed(step),
                                        rng=fold_in(base_key, step))
                    overflow.append(jnp.copy(machine.bundle.event_overflow))
                step += 1
                if len(overflow) > tr["depth"]:
                    with TraceAnnotation("bench.wait"):
                        overflow[-1 - tr["depth"]].block_until_ready()
                if time.perf_counter() - t0 >= seconds:
                    break
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(machine.bundle)
            elapsed = time.perf_counter() - t0
    compiles.active = False

    steps = step - warm
    per_step = np.diff([0] + [int(v) for v in overflow])
    peak = harness.memory_peak_bytes(devices)
    final = machine.bundle.state.ta_state
    words_differ = int(jnp.sum(
        machine.bundle.caches["bitpack"]
        != ref.pack_include(final, n_states=tm["n_states"])))
    del machine, final
    gc.collect()

    # the reference: the state again from the seed, the warm steps replayed
    # one at a time, each against its snapshot and against the step before
    have = sharded_state(tm, proto, avg_len, seed, sharding)
    cells_differ, changed = 0, []
    for k, snap in enumerate(snapshots):
        want = ref.train_steps(have, [feed(k)], [fold_in(base_key, k)],
                               tm)[0]
        changed.append(int(jnp.sum(want != have)))
        cells_differ = max(cells_differ, int(jnp.sum(
            want != jax.device_put(snap, sharding))))
        have = want
    print(f"reference TA cells changed per warm step: {changed}",
          file=sys.stderr, flush=True)

    return harness.Outcome(
        window_start=window_start,
        attempted=steps * batch,
        failed=int(np.sum(per_step > 0)) * batch,
        metrics={"train_samples_per_s": steps * batch / elapsed},
        checks=[
            harness.Check("ta_cells_differ", cells_differ, 0),
            harness.Check("reference_idle_steps",
                          sum(c == 0 for c in changed), 0),
            harness.Check("bitpack_words_differ", words_differ, 0),
            harness.Check("window_programs", compiles.count, 0),
        ],
        counters={"steps": steps, "samples": steps * batch,
                  "window_s": elapsed, "chips": shards,
                  "clauses_per_chip": n_local,
                  "reference_cells_changed_min": min(changed)},
        memory_peak_bytes=peak,
    )
