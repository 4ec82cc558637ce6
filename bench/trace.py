"""From a profiler trace (xplane) to the numbers the per-layer metrics read.

A traced run wraps its measured window in the host span ``bench.window``
and the host work inside it in other ``bench.*`` spans
(``jax.profiler.TraceAnnotation``). ``reduce`` then computes, inside that
window:

* the device's busy time — the union of the intervals in which an
  operation ran on a device's ``XLA Ops`` line — averaged over devices;
* the device time and count of every operation (keyed by its event name,
  on TPU the HLO instruction text) and of every executable (``XLA
  Modules`` line);
* the idle gaps of the device union, each named by the ``bench.*`` host
  span that overlaps it most (``host:none`` where none does);
* the ``breakdown`` a result line carries: the ten operations that took
  most device time and the ten longest idle gaps.

Operations are matched by a regular expression over their name and the
text of their stats (program name, long name), so a kernel is found by the
name it is given wherever the compiler puts it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class OpTotal:
    """Device time of one operation (or executable) inside the window."""

    name: str
    text: str          # name plus its stats' text, for matching
    seconds: float = 0.0
    count: int = 0


@dataclasses.dataclass
class TraceSummary:
    """What the per-layer readers get from one traced window."""

    window_s: float
    busy_s: float
    n_devices: int
    ops: dict           # event name → OpTotal
    modules: dict       # event name → OpTotal
    gaps: list          # [(seconds, host span name)], longest first

    def _match(self, table: dict, pattern: str) -> tuple[float, int] | None:
        rx = re.compile(pattern)
        hits = [t for t in table.values() if rx.search(t.text)]
        if not hits:
            return None
        return (sum(t.seconds for t in hits), sum(t.count for t in hits))

    def op(self, pattern: str) -> tuple[float, int] | None:
        """(device seconds, count) of the operations matching ``pattern``,
        or None where none ran in the window."""
        return self._match(self.ops, pattern)

    def module(self, pattern: str) -> tuple[float, int] | None:
        """(device seconds, count) of the executables matching
        ``pattern``, or None where none ran in the window."""
        return self._match(self.modules, pattern)

    @property
    def idle_share(self) -> float:
        """1 − busy / window."""
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, k: int = 10) -> dict:
        """The ``breakdown`` of a result line: top operations by device
        time, and the longest idle gaps by what the host was doing."""
        ops = sorted(self.ops.values(), key=lambda t: -t.seconds)[:k]
        return {"device_ops": [[short_name(t.name), t.seconds] for t in ops],
                "idle_gaps": [[name, s] for s, name in self.gaps[:k]]}


def short_name(name: str) -> str:
    """An HLO op's event name cut to its instruction, result and op kind
    (``%copy = s32[10,2000,1568]{2,1,0:T(8,128)} copy``)."""
    eq = name.find(" = ")
    if eq >= 0:
        i, depth = eq + 3, 0
        while i < len(name):            # past the result shape (or tuple)
            depth += {"(": 1, ")": -1}.get(name[i], 0)
            if name[i] == " " and depth == 0:
                break
            i += 1
        paren = name.find("(", i)
        if paren > 0:
            name = name[:paren]
    return name[:160]


def find_xplane(log_dir: str) -> str:
    """The one ``*.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(log_dir: str):
    """``ProfileData`` of the trace written under ``log_dir``."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(log_dir))


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _stat_text(event) -> str:
    return " ".join(str(v) for _, v in event.stats if isinstance(v, str))


def _collect(line, w0, w1, table, intervals):
    for ev in line.events:
        s = ev.start_ns
        e = s + ev.duration_ns
        if e <= w0 or s >= w1:
            continue
        s, e = max(s, w0), min(e, w1)
        if intervals is not None:
            intervals.append((s, e))
        t = table.get(ev.name)
        if t is None:
            t = table[ev.name] = OpTotal(name=ev.name,
                                         text=f"{ev.name} {_stat_text(ev)}")
        t.seconds += (e - s) * 1e-9
        t.count += 1


def reduce(profile, *, device_prefix: str = DEVICE_PREFIX) -> TraceSummary:
    """Reduce a ``ProfileData`` to a ``TraceSummary`` of its
    ``bench.window`` span."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise RuntimeError(f"no {WINDOW_SPAN!r} host span in the trace")
    w0, w1 = windows[0]
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN
             and sp[2] > w0 and sp[1] < w1]

    ops: dict = {}
    modules: dict = {}
    busy, all_intervals, n_devices = [], [], 0
    for plane in profile.planes:
        if not plane.name.startswith(device_prefix):
            continue
        intervals: list = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                _collect(line, w0, w1, ops, intervals)
            elif line.name == MODULES_LINE:
                _collect(line, w0, w1, modules, None)
        if not intervals:
            continue
        n_devices += 1
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged))
        all_intervals.extend(intervals)
    if not n_devices:
        raise RuntimeError(f"no operation ran on a {device_prefix}* device "
                           "inside the window")

    merged = _union(all_intervals)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    spans.sort(key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    gaps = []
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_overlap = "host:none", 0.0
        i = bisect.bisect_left(starts, g1) - 1
        while i >= 0 and starts[i] >= g0 - longest:
            name, s, e = spans[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
            i -= 1
        gaps.append(((g1 - g0) * 1e-9, best))
    gaps.sort(key=lambda g: -g[0])
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=sum(busy) / n_devices * 1e-9,
                        n_devices=n_devices, ops=ops, modules=modules,
                        gaps=gaps)
