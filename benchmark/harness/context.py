"""The state of one run, handed to the kind's loop and then to the
per-layer readers."""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field


@dataclass
class Run:
    cell: dict                  # the workloads entry
    config: dict                # the configuration's file
    traffic: dict               # the traffic mix's file
    seed: int
    seconds: float              # length of the measured window
    trace: bool
    rehearsal: bool             # CPU rehearsal: no device metric is printed
    t_start: float              # time.time() at process start
    devices: list
    compile_log: object
    scratch: str                # benchmark/.cache/<cell>/, made anew
    phases: dict = field(default_factory=dict)   # host-clock seconds
    facts: dict = field(default_factory=dict)    # what the kind learned
    events: list = field(default_factory=list)   # program telemetry
    window: object = None       # trace_reduce.Window of the steady stretch

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.time()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) \
                + time.time() - t0
