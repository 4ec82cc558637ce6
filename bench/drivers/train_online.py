"""Online training: the trainer is its own client (a closed loop).

``TsetlinMachine.partial_fit`` runs sequential learning (the paper's
semantics) on ``batch`` samples per step, drawn in a seeded order from a
pool of labelled samples and sent from the host each step. The machine
maintains the caches the traffic names, with an event buffer sized to the
step's worst case (``min(m, 2·batch)·n·2o`` cells), so no event can
overflow and a stale cache is never measured.

Set-up (counted in ``setup_s``): the pool and the TA state from the seed,
the machine with that state, and its first ``warm_steps`` steps through the
window's own call and feed (the first compiles, or loads the step from the
persistent cache). The window then steps the same machine until
``seconds`` have passed and ends on a blocked step; at most ``depth``
steps are in flight.

Correct: the TA state after each warm step equals the plain reference's
(``bench/ref.py``) replay of the same samples and keys; after the window
the maintained bitpack cache equals a fresh pack of the final state; no
program is compiled or loaded in the window.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness, ref
from bench.gen import data, state


def run(cell: harness.Cell, seed: int, seconds: float,
        trace_dir: str | None, devices) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core import TMState, Topology, TsetlinMachine
    from repro.core.types import TMConfig

    tm, tr = cell.tm, cell.traffic
    m, n_cl, o = tm["n_classes"], tm["n_clauses"], tm["n_features"]
    batch, warm = tr["batch"], tr["warm_steps"]
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
    max_events = min(m, 2 * batch) * n_cl * 2 * o
    machine = TsetlinMachine(
        TMConfig(**tm), topology=Topology(engines=tuple(tr["engines"])),
        parallel=False, max_events_per_batch=max_events)
    avg_len = cell.config["state"]["avg_clause_len"]
    machine.bundle = machine.session.prepare(
        TMState(ta_state=state.make_state(tm, proto, avg_len, seed)))

    snapshots = []
    for step in range(warm):
        machine.partial_fit(*feed(step), rng=fold_in(base_key, step))
        snapshots.append(jnp.copy(machine.bundle.state.ta_state))
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
    ta = state.make_state(tm, proto, avg_len, seed)
    want = ref.train_steps(ta, [feed(k) for k in range(warm)],
                           [fold_in(base_key, k) for k in range(warm)], tm)
    cells_differ = max(int(jnp.sum(a != b)) for a, b in zip(snapshots, want))

    return harness.Outcome(
        window_start=window_start,
        attempted=steps * batch,
        failed=int(np.sum(per_step > 0)) * batch,
        metrics={"train_samples_per_s": steps * batch / elapsed},
        checks=[
            harness.Check("ta_cells_differ", cells_differ, 0),
            harness.Check("bitpack_words_differ", words_differ, 0),
            harness.Check("window_programs", compiles.count, 0),
        ],
        counters={"steps": steps, "samples": steps * batch,
                  "window_s": elapsed},
        memory_peak_bytes=peak,
    )
