"""Device identity, the table of peaks, compile counting, peak memory."""
from __future__ import annotations

import json
import os
import sys
import time

from fedbench.reference import BENCH


def require_accelerator(chips: int):
    """The devices of a run: a TPU with at least ``chips`` chips, or exit
    with a non-zero code before any result is printed."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX runs on {devices[0].platform!r})")
    if len(devices) < chips:
        sys.exit(f"bench: {chips} chips asked for, {len(devices)} found")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileStats:
    """Counts persistent-cache hits and misses and backend compiles, with
    the host time of each, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.events: list = []         # (perf_counter, kind)
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events.append((time.perf_counter(), event.rsplit("/", 1)[1]))

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.events.append((time.perf_counter(), "backend_compile"))

    def count(self, kind: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> int:
        return sum(1 for t, k in self.events if k == kind and lo <= t <= hi)

    def within(self, lo: float, hi: float) -> int:
        """Compiles and cache loads between ``lo`` and ``hi``."""
        return sum(1 for t, _ in self.events if lo <= t <= hi)
