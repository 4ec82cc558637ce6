"""Readings that set each limit of ``correct``: sound runs, the control, the
faults.

    python3 bench/control.py --workload <name> --mode <mode> --seeds 1,2,3 [--seconds 2]

Runs the cell's driver once per seed in one process (set-up is paid once
per seed, compiles once) and prints every number compared, per seed, as
one JSON line. ``--mode``:

* ``sound`` — the program as it is (the lower readings);
* ``control`` — the plain reference in the program's place, one precision
  below what the configuration states: serving scores computed from the TA
  state held in int8 (the next integer type below the configuration's
  int16, where states above 127 wrap), training with its Type I uniforms
  rounded to bfloat16 (the float type below float32);
* ``fault_answer`` — one answer altered where the server produces it;
* ``fault_unchanged`` — the train step returns its state unchanged;
* ``fault_half`` — the train step learns from half of its batch only.

The patches are context managers, so the tests under ``bench/tests`` drive
the same faults at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, ref  # noqa: E402

@contextlib.contextmanager
def serve_control(tm: dict):
    """Every batch scored by the reference from an int8 TA state."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import runtime

    def dispatch(self, reqs):
        b = runtime.bucket_for(len(reqs), self.sizes)
        xp = np.zeros((b, self.n_features), np.uint8)
        for i, r in enumerate(reqs):
            xp[i] = r.x
        dev = ref.scores(self.bundle.state.ta_state, jnp.asarray(xp),
                         n_states=tm["n_states"], state_dtype=jnp.int8)
        self.batches += 1
        self.rows_real += len(reqs)
        self.rows_padded += b
        return runtime._Inflight(device_scores=dev, requests=reqs, bucket=b)

    with mock.patch.object(runtime.AsyncTMServer, "dispatch", dispatch):
        yield


@contextlib.contextmanager
def serve_fault_answer(tm: dict, every: int = 64):
    """The first answer of every ``every``-th completed batch gets +1 on
    its first class, as the server resolves it."""
    import numpy as np

    from repro.serving import runtime
    orig = runtime.AsyncTMServer.complete
    seen = [0]

    def complete(self, item):
        seen[0] += 1
        if seen[0] % every == 0:
            host = np.array(item.device_scores)
            host[0, 0] += 1
            item = runtime._Inflight(device_scores=host,
                                     requests=item.requests,
                                     bucket=item.bucket)
        return orig(self, item)

    with mock.patch.object(runtime.AsyncTMServer, "complete", complete):
        yield


@contextlib.contextmanager
def train_control(tm: dict):
    """Each step computed by the reference with bfloat16 uniforms."""
    import jax.numpy as jnp

    from repro.core import TMState, TsetlinMachine

    def partial_fit(self, xs, ys, rng=None, *, mask=None):
        ta = ref.train_step(
            self.bundle.state.ta_state, jnp.asarray(xs), jnp.asarray(ys),
            rng, n_states=tm["n_states"], s=float(tm["s"]),
            threshold=int(tm["threshold"]),
            boost_true_positive=tm["boost_true_positive"],
            uniform_dtype=jnp.bfloat16).astype(jnp.int16)
        self.bundle = self.session.prepare(TMState(ta_state=ta))
        return self

    with mock.patch.object(TsetlinMachine, "partial_fit", partial_fit):
        yield


@contextlib.contextmanager
def train_fault_unchanged(tm: dict):
    """The step hands back the state it was given."""
    from repro.core import TsetlinMachine

    def partial_fit(self, xs, ys, rng=None, *, mask=None):
        return self

    with mock.patch.object(TsetlinMachine, "partial_fit", partial_fit):
        yield


@contextlib.contextmanager
def train_fault_half(tm: dict):
    """The step learns from the first half of its batch only (the rest is
    masked out, so the compiled shape does not change)."""
    import jax.numpy as jnp

    from repro.core import TsetlinMachine
    orig = TsetlinMachine.partial_fit

    def partial_fit(self, xs, ys, rng=None, *, mask=None):
        half = jnp.arange(len(xs)) < len(xs) // 2
        return orig(self, xs, ys, rng, mask=half)

    with mock.patch.object(TsetlinMachine, "partial_fit", partial_fit):
        yield


def _sound(tm: dict):
    return contextlib.nullcontext()


# driver kind → mode → patch
PATCHES = {
    "serve_open_loop": {"sound": _sound, "control": serve_control,
                        "fault_answer": serve_fault_answer},
    "train_online": {"sound": _sound, "control": train_control,
                     "fault_unchanged": train_fault_unchanged,
                     "fault_half": train_fault_half},
}


def patch(mode: str, driver: str, tm: dict):
    """The context manager that puts ``mode`` in the program's place."""
    return PATCHES[driver][mode](tm)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=sorted({m for p in PATCHES.values() for m in p}))
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    devices = harness.require_chip(cell.chips)
    harness.enable_compile_cache()
    kind = cell.traffic["driver"]
    if args.mode not in PATCHES[kind]:
        raise SystemExit(f"mode {args.mode!r} does not apply to {kind}")
    driver = harness.load_module(cell.driver, "bench_driver")
    for seed in (int(s) for s in args.seeds.split(",")):
        with patch(args.mode, kind, cell.tm):
            out = driver.run(cell, seed, args.seconds, None, devices)
        print(json.dumps({
            "workload": args.workload, "mode": args.mode, "seed": seed,
            "correct": out.correct, "failed": out.failed,
            "checks": {c.name: c.value for c in out.checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
