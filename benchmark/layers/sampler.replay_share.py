"""Device busy time of the replay over ALL training rows (a tree grown on
a sample of the rows: its route log replayed by ``route_pass`` launches
over the full bin matrix, then the ``table_lookup`` that turns every row's
leaf into its score's delta, both under ``lgbm.score_update``) over device
busy time, in per cent, mean over the chips. None where no ``route_pass``
ran under that scope: a program that grows on all rows has every row's
leaf from the grower itself and replays nothing."""
import os

from harness import trace_phases, trace_reduce

PHASE = "score_update"
REPLAY, LOOKUP = "route_pass", "table_lookup"


def read(run):
    if run.window is None:
        return None
    named = trace_phases.names(
        trace_reduce.find_xplane(os.path.join(run.scratch, "trace")))
    shares, replayed = [], False
    for dev in run.window.reduced.devices:
        ns = 0
        for phase, pallas, name, op_ns, _, _ in trace_phases.phased_ops(
                run.window, dev, named.get(dev.name, {})):
            kernel = name.rsplit(".", 1)[0]
            if pallas and trace_phases.top_phase(phase) == PHASE \
                    and kernel in (REPLAY, LOOKUP):
                ns += op_ns
                replayed |= kernel == REPLAY
        shares.append(ns / run.window.busy_ns(dev))
    return 100.0 * sum(shares) / len(shares) if replayed else None
