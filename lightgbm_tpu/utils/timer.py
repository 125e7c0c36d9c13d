"""Named-section timing + profiler integration.

Behavioral analog of the reference's TIMETAG-gated section timer
(ref: include/LightGBM/utils/common.h:978 Timer, :1042 FunctionTimer —
Start/Stop accumulate per-name wall time, printed once at shutdown).
Disabled timers are no-ops, so instrumentation can stay in the hot
driver paths permanently like the reference's.

Enable with env ``LIGHTGBM_TPU_TIMETAG=1`` (the analog of compiling the
reference with -DTIMETAG) or ``global_timer.enable()``. On-device work is
asynchronous under JAX, so sections measure DISPATCH time unless
``sync=True`` is passed, which blocks on the given arrays first — the
honest way to attribute device time to a section.

Every section is also a ``jax.profiler.TraceAnnotation``, whether the
timer is enabled or not: in a profiler trace (``profile_dir``,
docs/Observability.md) the sections lie on the host plane, on the same
clock as the device's operations. Outside a profiler session an
annotation costs one atomic load.
"""
from __future__ import annotations

import atexit
import contextlib
import os
import threading
import time
from typing import Dict, NamedTuple

from jax.profiler import TraceAnnotation

from . import log


class SectionStat(NamedTuple):
    """Accumulated cost of one named section."""
    total: float
    count: int


class Timer:
    """Accumulates wall-clock per named section (thread-safe)."""

    def __init__(self, enabled: bool = False):
        self._enabled = enabled
        self._lock = threading.Lock()
        self._acc: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._tls = threading.local()
        # bumped by reset(): invalidates every thread's open-start stack,
        # so a section started before reset() cannot leak a stale start
        # time into the next run
        self._gen = 0

    # ------------------------------------------------------------------
    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    def reset(self) -> None:
        with self._lock:
            self._acc.clear()
            self._counts.clear()
            self._gen += 1

    # ------------------------------------------------------------------
    def _stack(self) -> Dict[str, float]:
        """This thread's open-start stack, discarded when a reset() has
        happened since it was last touched."""
        tls = self._tls
        if getattr(tls, "gen", None) != self._gen:
            tls.stack = {}
            tls.gen = self._gen
        return tls.stack

    def start(self, name: str) -> None:
        if not self._enabled:
            return
        self._stack()[name] = time.perf_counter()

    def stop(self, name: str) -> None:
        if not self._enabled:
            return
        t0 = self._stack().pop(name, None)
        if t0 is None:
            return
        self.add(name, time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        """Accumulate an externally-measured duration (used by callers
        that time once and feed both this timer and the telemetry
        registry)."""
        if not self._enabled:
            return
        with self._lock:
            self._acc[name] = self._acc.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1

    @contextlib.contextmanager
    def section(self, name: str, sync=None):
        """Time a block. ``sync`` = array/pytree to block on before
        closing the section (attributes asynchronous device work here)."""
        with TraceAnnotation(name):
            self.start(name)
            try:
                yield
            finally:
                if self._enabled and sync is not None:
                    import jax
                    jax.block_until_ready(sync)
                self.stop(name)

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, SectionStat]:
        """Per-section (total_seconds, call_count)."""
        with self._lock:
            return {name: SectionStat(self._acc[name],
                                      self._counts.get(name, 0))
                    for name in self._acc}

    def print(self) -> None:
        """(ref: common.h:1011 Timer::Print — '%s costs: %f' per name;
        costliest first so the hot section tops the report)"""
        if not self._acc:
            return
        for name in sorted(self._acc, key=self._acc.get, reverse=True):
            log.info("%s costs: %f seconds (%d calls)", name,
                     self._acc[name], self._counts.get(name, 0))


global_timer = Timer(enabled=bool(int(
    os.environ.get("LIGHTGBM_TPU_TIMETAG", "0") or "0")))


@atexit.register
def _print_at_exit() -> None:  # ref: common.h:988 ~Timer() { Print(); }
    if global_timer.enabled:
        global_timer.print()
