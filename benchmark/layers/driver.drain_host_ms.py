"""Median, over the chunk boundaries in the trace, of the host time the
program's thread spends in its ``GBDT::Drain::*`` sections other than
``Fetch`` (which waits for the device) between the end of one run of the
training step and the start of the next: the part of
``driver.chunk_gap_ms`` that is the drain's own host work."""
import statistics

from harness import trace_phases


def read(run):
    if run.window is None:
        return None
    reduced = run.window.reduced
    work = [sum(trace_phases.host_section_ns(
        reduced, t0, t1, trace_phases.is_drain_work).values())
        for t0, t1 in trace_phases.chunk_boundaries(reduced)]
    # a program without the sections (the parent of the PR that brought
    # them) has nothing to read: no number, not zero
    return statistics.median(work) / 1e6 if any(work) else None
