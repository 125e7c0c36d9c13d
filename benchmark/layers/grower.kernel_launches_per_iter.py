"""Pallas kernel launches (custom-call events on the first chip) in the
traced chunk, per boosting iteration: an exact count."""
from harness import trace_reduce


def read(run):
    if run.window is None:
        return None
    dev = run.window.reduced.devices[0]
    launches = run.window.count(dev, dev.ops.where(trace_reduce.is_pallas))
    return launches / run.facts["chunk_iterations"]
