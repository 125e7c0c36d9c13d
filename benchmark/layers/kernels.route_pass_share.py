"""Device time inside ``route_pass`` launches (the grower's route-only
levels and the validation replay) over device busy time, in per cent, mean
over the chips. In the table form of the routing (``W @ one_hot``:
categorical, bundled and wide-bin jobs) a launch builds the whole one-hot
and costs about as much as half a histogram pass; in the bins form it is
under 1 % of the step: the number says which form ran."""
from harness import trace_reduce

KERNEL = "route_pass"


def read(run):
    w = run.window
    if w is None:
        return None
    shares = [w.busy_ns(d, d.ops.where(
        lambda op: trace_reduce.is_pallas(op)
        and trace_reduce.kernel_of(op) == KERNEL)) / w.busy_ns(d)
        for d in w.reduced.devices]
    return 100.0 * sum(shares) / len(shares)
