"""Device busy time under the ``cat`` scope (the categorical split search:
the sort of every leaf's categories by gradient over hessian and the two
prefix scans, inside ``lgbm.grow`` > ``root`` and > ``level`` > ``split``;
docs/Observability.md section 3a) over device busy time, in per cent, mean
over the chips. ``trace_phases.phase_of`` folds the scope into its stage;
this reads it from the same ``op_name``s. A program without the scope (the
parent of the PR that brought it) gives None, not zero."""
import os

from harness import trace_phases, trace_reduce

GROW = trace_phases.PREFIX + "grow/"
SCOPE = "cat"


def under_cat(op_name: str) -> bool:
    """``.../lgbm.grow/.../level/.../split/cat/sort`` -> True: ``cat`` is a
    scope (never the last component, which is the primitive) of an
    operation whose innermost ``lgbm.`` scope is the grower's."""
    at = op_name.rfind(trace_phases.PREFIX)
    if at < 0 or not op_name.startswith(GROW, at):
        return False
    return SCOPE in op_name[at + len(GROW):].split("/")[:-1]


def read(run):
    if run.window is None:
        return None
    named = trace_phases.names(
        trace_reduce.find_xplane(os.path.join(run.scratch, "trace")))
    shares, found = [], False
    for dev in run.window.reduced.devices:
        ns = 0
        for _, _, _, op_ns, _, known in trace_phases.phased_ops(
                run.window, dev, named.get(dev.name, {})):
            if known and under_cat(known.op_name):
                ns += op_ns
                found = True
        shares.append(ns / run.window.busy_ns(dev))
    return 100.0 * sum(shares) / len(shares) if found else None
