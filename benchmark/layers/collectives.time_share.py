"""Device time in cross-chip operations (all-reduce and its kin,
synchronous or asynchronous) over device busy time, in per cent, mean
over the chips."""


def read(run):
    w = run.window
    if w is None or len(w.reduced.devices) < 2:
        return None
    shares = [w.collective_ns(d)[0] / w.busy_ns(d) for d in w.reduced.devices]
    return 100.0 * sum(shares) / len(shares)
