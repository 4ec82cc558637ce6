"""What every cell shares: finding it by name, the chip, the cache, the
result line.

``BENCHMARK.json`` names everything; this module finds each piece by that
name and nothing else:

* a configuration — the ``file`` its ``configs`` entry names;
* a traffic mix — ``bench/traffic/<traffic>.json``, whose ``driver`` names
  ``bench/drivers/<driver>.py`` (a module with ``run(cell, seed, seconds,
  trace_dir) -> Outcome``);
* a per-layer metric — ``bench/metrics/<name>.py`` (a module with
  ``read(ctx) -> float | None``).

A missing file is an error, never a default.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json``, with everything it names."""

    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    driver: Path
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list       # entries with the reader's path under "reader"

    @property
    def tm(self) -> dict:
        """The configuration's ``TMConfig`` fields."""
        return self.config["tm"]


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit (correct iff value <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver's run hands back to ``run.py``."""

    window_start: float           # time.monotonic() when the window opened
    attempted: int
    failed: int
    metrics: dict                 # end-to-end name → value
    checks: list                  # [Check]
    counters: dict                # what per-layer readers read besides the trace
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one Python file by path (drivers and metric readers)."""
    if not path.is_file():
        raise FileNotFoundError(f"no module file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    """``BENCHMARK.json`` at the checkout's root."""
    return _load_json(root / "BENCHMARK.json", "benchmark")


def find_cell(name: str, bench: dict | None = None,
              root: Path = ROOT) -> Cell:
    """Everything the workload ``name`` names, found by name."""
    bench = bench if bench is not None else benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names config {w['config']!r}, "
                       "which BENCHMARK.json does not list")
    config = _load_json(root / configs[w["config"]]["file"],
                        f"config {w['config']!r}")
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']!r}")
    driver = root / "bench" / "drivers" / f"{traffic['driver']}.py"
    if not driver.is_file():
        raise FileNotFoundError(f"traffic {w['traffic']!r} names driver "
                                f"{traffic['driver']!r}: no file {driver}")

    def mine(entry):
        return name in entry.get("workloads", [name])

    per_layer = []
    for entry in bench["per_layer"]:
        if not mine(entry):
            continue
        reader = root / "bench" / "metrics" / f"{entry['name']}.py"
        if not reader.is_file():
            raise FileNotFoundError(f"per-layer metric {entry['name']!r}: "
                                    f"no reader {reader}")
        per_layer.append({**entry, "reader": reader})
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver,
                end_to_end=[e for e in bench["end_to_end"] if mine(e)],
                per_layer=per_layer)


def require_chip(chips: int):
    """The devices to run on; raises ``NoChip`` where JAX finds no TPU or
    fewer than ``chips`` of them. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; unknown kinds are an
    error."""
    table = _load_json(BENCH / "peaks.json", "peaks")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/``, every program cached, so that only a checkout's first
    run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache, while
    ``active``: either inside the measured window is a failure of the
    run."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@contextlib.contextmanager
def traced(trace_dir: str | None):
    """Profile the enclosed block into ``trace_dir`` (no-op when None),
    with the Python function tracer off."""
    if trace_dir is None:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))


def print_result(result: dict, checks: list) -> None:
    """The checks as the last lines on stderr, then the result as the last
    line on stdout, with ``checks`` as its last key."""
    for c in checks:
        print(f"check {c.name}: {c.value} (limit {c.limit}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)


def tmp_trace_dir() -> str:
    """A fresh directory for one run's trace, under ``$TMPDIR``."""
    import tempfile
    return tempfile.mkdtemp(prefix="bench-trace-",
                            dir=os.environ.get("TMPDIR"))
