"""From a profiler trace to device numbers.

``capture`` runs the JAX profiler around the window and reads the
``.xplane.pb`` it writes back into plain tuples; the reductions below work
on those tuples only, so a test can feed them a synthetic trace:

* ``Event(device, line, name, t0, t1)`` — seconds on the trace's clock;
  ``line`` is ``"ops"`` (one XLA operation) or ``"modules"`` (one
  compiled program, by its stable jit name, e.g. ``jit_masked_sum``).
* ``busy_s`` — union of the device's operation intervals inside the
  window, averaged over devices.
* ``module_s`` — device time of one program by its jit name, clipped to
  the window.
* ``top_ops`` / ``idle_gaps`` — the ``breakdown``: device operations by
  total time, and the longest gaps in which no operation ran, each named
  by the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from collections import defaultdict
from typing import NamedTuple

MARK = "fedbench.clock"
LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}


class Event(NamedTuple):
    device: str
    line: str
    name: str
    t0: float
    t1: float


class Capture:
    """Profiler session; ``events`` and ``offset`` are set by ``stop``.

    ``offset`` maps the host clock onto the trace's: trace time =
    ``time.perf_counter() + offset``, from a marker annotation.
    """

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="fedbench-trace-")
        self.events: list = []
        self.offset = None
        self.summary: dict = {}

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._mark()

    def _mark(self):
        import jax
        with jax.profiler.TraceAnnotation(MARK):
            self._mark_t = time.perf_counter()

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        try:
            self._read()
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def _read(self):
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        data = ProfileData.from_file(sorted(files)[-1])
        mark = None
        for plane in data.planes:
            lines = list(plane.lines)
            self.summary[plane.name] = sorted({ln.name for ln in lines})
            device = plane.name.startswith("/device:")
            for line in lines:
                kind = LINES.get(line.name) if device else None
                if kind is None and (device or mark is not None):
                    continue
                for ev in line.events:
                    if kind is not None:
                        t0 = ev.start_ns * 1e-9
                        self.events.append(Event(
                            plane.name, kind, short_name(ev.name), t0,
                            t0 + ev.duration_ns * 1e-9))
                    elif ev.name == MARK:
                        mark = ev.start_ns * 1e-9
                        break
        if mark is None:
            raise RuntimeError("clock marker missing from the trace")
        self.offset = mark - self._mark_t


def short_name(name: str) -> str:
    """An operation's name without the HLO text the trace appends:
    ``"%fusion.12 = bf16[...] fusion(...)"`` -> ``"fusion.12"``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _clip(t0, t1, lo, hi):
    return max(t0, lo), min(t1, hi)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(t0, t1)`` intervals, clipped to ``[lo, hi]``."""
    out = []
    for t0, t1 in sorted(_clip(a, b, lo, hi) for a, b in intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return [tuple(iv) for iv in out]


def devices(events) -> list:
    return sorted({e.device for e in events if e.line == "ops"})


def busy_s(events, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = devices(events)
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        ivs = union([(e.t0, e.t1) for e in events
                     if e.device == d and e.line == "ops"], lo, hi)
        total += sum(b - a for a, b in ivs)
    return total / len(devs)


def module_s(events, name: str, lo: float, hi: float) -> float:
    """Device seconds of the program ``name`` (its jit name, whatever
    fingerprint the trace adds in brackets) inside the window, summed
    over devices."""
    total = 0.0
    for e in events:
        if e.line == "modules" and e.name.split("(", 1)[0] == name:
            a, b = _clip(e.t0, e.t1, lo, hi)
            total += max(0.0, b - a)
    return total


def top_ops(events, lo: float, hi: float, k: int = 10,
            line: str = "ops") -> list:
    """``[name, seconds]`` of the ``k`` names with most device time."""
    by = defaultdict(float)
    for e in events:
        if e.line == line:
            a, b = _clip(e.t0, e.t1, lo, hi)
            if b > a:
                by[e.name] += b - a
    return sorted(([n, s] for n, s in by.items()), key=lambda x: -x[1])[:k]


def idle_gaps(events, lo: float, hi: float, spans, k: int = 10) -> list:
    """Longest gaps on the first device with no operation running, each
    named by the innermost span ``(name, t0, t1)`` (trace clock) that
    covers the gap's midpoint, or ``"no span"``."""
    devs = devices(events)
    if not devs:
        return []
    busy = union([(e.t0, e.t1) for e in events
                  if e.device == devs[0] and e.line == "ops"], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (a + b)
        inside = [s for s in spans if s[1] <= mid <= s[2]]
        name = (min(inside, key=lambda s: s[2] - s[1])[0] if inside
                else "no span")
        out.append([name, b - a])
    return out
