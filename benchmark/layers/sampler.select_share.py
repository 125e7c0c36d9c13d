"""Device busy time under ``lgbm.sample`` > ``select`` and > ``draw`` (the
sampler of a ``boosting=goss`` job: the counting passes that find the k-th
largest |g * h|, the hashed keys and the second select;
docs/Observability.md section 3a) over device busy time, in per cent, mean
over the chips. A program without the scope (one that samples on the host,
or not at all) gives None, not zero."""
import os

from harness import trace_phases, trace_reduce

SAMPLE = trace_phases.PREFIX + "sample/"
STAGES = ("select", "draw")


def under(op_name: str, stages) -> bool:
    """``.../lgbm.sample/select/reduce_sum`` -> True for ``select``: the
    stage is a scope (never the last component, which is the primitive) of
    an operation whose innermost ``lgbm.`` scope is the sampler's."""
    at = op_name.rfind(trace_phases.PREFIX)
    if at < 0 or not op_name.startswith(SAMPLE, at):
        return False
    inner = op_name[at + len(SAMPLE):].split("/")[:-1]
    return any(stage in inner for stage in stages)


def share(run, stages):
    if run.window is None:
        return None
    named = trace_phases.names(
        trace_reduce.find_xplane(os.path.join(run.scratch, "trace")))
    shares, found = [], False
    for dev in run.window.reduced.devices:
        ns = 0
        for _, _, _, op_ns, _, known in trace_phases.phased_ops(
                run.window, dev, named.get(dev.name, {})):
            if known and under(known.op_name, stages):
                ns += op_ns
                found = True
        shares.append(ns / run.window.busy_ns(dev))
    return 100.0 * sum(shares) / len(shares) if found else None


def read(run):
    return share(run, STAGES)
