"""Device time inside Pallas kernels over device busy time, in per cent,
mean over the chips."""
from harness import trace_reduce


def read(run):
    w = run.window
    if w is None:
        return None
    shares = [w.busy_ns(d, d.ops.where(trace_reduce.is_pallas))
              / w.busy_ns(d) for d in w.reduced.devices]
    return 100.0 * sum(shares) / len(shares)
