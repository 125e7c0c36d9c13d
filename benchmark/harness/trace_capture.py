"""Taking the profiler's trace from the benchmark's side.

``ChunkTrace`` is for a job that is ONE blocking call (``lgb.train``): a
helper thread follows the program's telemetry stream and starts the
profiler once it has shown ``start_after`` ``megastep`` events (the end
of the warm-up), then stops it ``settle_s`` after it has shown
``stop_after``, so that the trace holds whole steady chunks and the
start of the one that follows. While it waits it also reads each
device's ``bytes_in_use``: ``in_use_peak`` is the most each device held
at any look inside that stretch, which is what the job keeps on the chip
while it is measured (``peak_bytes_in_use`` is the most it ever held,
set-up included).
"""
from __future__ import annotations

import os
import re
import threading
import time


_MEGASTEP = re.compile(r'"event"\s*:\s*"megastep"')


def _options():
    import jax.profiler as jp
    opts = jp.ProfileOptions()
    opts.host_tracer_level = 2
    # Python function events are what label an idle gap with the
    # program's own function (drain, callback replay, dispatch)
    opts.python_tracer_level = 1
    return opts


class ChunkTrace:
    def __init__(self, telemetry_path: str, out_dir: str, start_after: int,
                 stop_after: int, devices: list, settle_s: float = 0.5,
                 poll_s: float = 0.05):
        self.path, self.out_dir = telemetry_path, out_dir
        self.start_after, self.stop_after = start_after, stop_after
        self.devices = devices
        self.settle_s, self.poll_s = settle_s, poll_s
        self.in_use_peak = [0] * len(devices)
        self.error = None
        self.started_at = self.stopped_at = None
        self._seen, self._offset = 0, 0
        self._quit = threading.Event()
        self._thread = threading.Thread(target=self._follow,
                                        name="bench-chunk-trace", daemon=True)

    def _look_at_memory(self) -> None:
        for i, d in enumerate(self.devices):
            now = int((d.memory_stats() or {}).get("bytes_in_use", 0))
            self.in_use_peak[i] = max(self.in_use_peak[i], now)

    def _wait_for(self, n: int, watch_memory: bool = False) -> bool:
        """Until the stream has shown ``n`` megastep events (reads only
        what was appended since the last look)."""
        while not self._quit.is_set():
            if watch_memory:
                self._look_at_memory()
            try:
                with open(self.path) as fh:
                    fh.seek(self._offset)
                    fresh = fh.read()
            except FileNotFoundError:
                fresh = ""
            whole = fresh[:fresh.rfind("\n") + 1]      # finished lines only
            self._offset += len(whole.encode())
            self._seen += len(_MEGASTEP.findall(whole))
            if self._seen >= n:
                return True
            self._quit.wait(self.poll_s)
        return False

    def _follow(self) -> None:
        import jax.profiler as jp
        try:
            if not self._wait_for(self.start_after):
                return
            os.makedirs(self.out_dir, exist_ok=True)
            jp.start_trace(self.out_dir, profiler_options=_options())
            self.started_at = time.time()
            try:
                if self._wait_for(self.stop_after, watch_memory=True):
                    self._quit.wait(self.settle_s)
            finally:
                jp.stop_trace()
                self.stopped_at = time.time()
        except Exception as exc:            # reported by __exit__
            self.error = exc

    def __enter__(self) -> "ChunkTrace":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._quit.set()
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise RuntimeError("the trace thread did not stop")
        if exc[0] is None:
            if self.error is not None:
                raise RuntimeError("tracing failed") from self.error
            if self.stopped_at is None:
                raise RuntimeError(
                    f"the job ended before {self.start_after} megastep "
                    "events: nothing was traced")
