"""1 - (union of operation intervals / traced window), in per cent, of
the idlest chip: it names the layer to look at and moves nothing
itself."""


def read(run):
    w = run.window
    if w is None:
        return None
    busy = min(w.busy_ns(d) for d in w.reduced.devices)
    return 100.0 * (1.0 - busy / (w.t1 - w.t0))
