"""What the harness watches while the program runs: JAX's own compile
and cache events, the program's telemetry stream, the devices' memory.
(``CompileLog`` is a copy of ``chip_smoke.CompileLog``: the yardstick
keeps its own.)"""
from __future__ import annotations

import json
import time


class CompileLog:
    """Backend compiles and persistent-cache traffic, wall-stamped, from
    ``jax.monitoring``. ("writes" is JAX's ``cache_misses`` event: it
    fires when an entry is written, not on every lookup that misses.)"""

    _CACHE = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self):
        from jax import monitoring
        self.backend = []           # (time.time(), seconds, fun_name)
        self.cache = []             # (time.time(), hits|writes)
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend.append((time.time(), float(seconds),
                                 str(kw.get("fun_name", "?"))))

    def _event(self, event, **_):
        if event in self._CACHE:
            self.cache.append((time.time(), self._CACHE[event]))

    def compiled_between(self, t0: float, t1: float) -> list:
        return [name for ts, _, name in self.backend if t0 < ts <= t1]

    def cache_traffic(self, t0: float = 0.0, t1: float = float("inf")):
        out = {"hits": 0, "writes": 0}
        for ts, key in self.cache:
            if t0 < ts <= t1:
                out[key] += 1
        return out


def read_events(path: str) -> list:
    """The program's telemetry JSONL as a list of dicts."""
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def of_kind(events: list, name: str) -> list:
    return [e for e in events if e.get("event") == name]


def memory_peaks(devices) -> list:
    """``peak_bytes_in_use`` of each device (0 where the backend reports
    none, as the CPU does)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]
