"""Median, over the chunk boundaries in the trace, of the time the device
spends between the end of one run of the training step and the start of
the next (the ``XLA Modules`` line of the first chip): the drain, the
callback replay and the next dispatch, as the device sees them."""
import statistics

from harness import trace_reduce


def read(run):
    if run.window is None:
        return None
    dev = run.window.reduced.devices[0]
    step = trace_reduce.step_runs(dev)
    gaps = [(dev.modules.start[b] - dev.modules.end[a]) / 1e6
            for a, b in zip(step[:-1], step[1:])]
    return statistics.median(gaps) if gaps else None
