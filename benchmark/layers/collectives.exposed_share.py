"""The part of cross-chip operation time during which no other operation
runs on that chip, over device busy time, in per cent, mean over the
chips: what overlap could still hide."""


def read(run):
    w = run.window
    if w is None or len(w.reduced.devices) < 2:
        return None
    shares = [w.collective_ns(d)[1] / w.busy_ns(d) for d in w.reduced.devices]
    return 100.0 * sum(shares) / len(shares)
