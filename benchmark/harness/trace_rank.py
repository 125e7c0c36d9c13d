"""Device time under the sub-scopes of ``lgbm.gradients`` that a ranking
objective names (``rank_sort``, ``rank_pairs``, ``rank_scatter``;
docs/Observability.md section 3a). ``trace_phases.phase_of`` folds them
into ``gradients``; the ranking readers need them apart, and read them
from the same ``op_name``s. A program without these scopes (the parent of
the PR that brought them) gives None, not zero."""
from __future__ import annotations

import os

from . import trace_phases, trace_reduce

GRADIENTS = trace_phases.PREFIX + "gradients/"


def stage_of(op_name: str) -> str:
    """``.../lgbm.gradients/rank_sort/sort`` -> ``rank_sort``; '' for an
    operation of the gradients with no stage, None for any other."""
    at = op_name.rfind(trace_phases.PREFIX)
    if at < 0 or not op_name.startswith(GRADIENTS, at):
        return None
    inner = op_name[at + len(GRADIENTS):].split("/")[:-1]
    return next((p for p in inner if p.startswith("rank_")), "")


def busy_by_stage(run):
    """[(device, {stage: busy ns})] of the traced window for the
    operations under ``lgbm.gradients``, or None: no window, or no
    ``rank_`` stage anywhere in the trace."""
    if run.window is None:
        return None
    named = trace_phases.names(
        trace_reduce.find_xplane(os.path.join(run.scratch, "trace")))
    out, staged = [], False
    for dev in run.window.reduced.devices:
        by = {}
        for _, _, _, ns, _, known in trace_phases.phased_ops(
                run.window, dev, named.get(dev.name, {})):
            stage = stage_of(known.op_name) if known else None
            if stage is not None:
                by[stage] = by.get(stage, 0) + ns
                staged = staged or bool(stage)
        out.append((dev, by))
    return out if staged else None


def share(run, pick):
    """Per cent of busy time, mean over the chips, of the stages that
    ``pick`` accepts."""
    table = busy_by_stage(run)
    if table is None:
        return None
    shares = [sum(ns for stage, ns in by.items() if pick(stage))
              / run.window.busy_ns(dev) for dev, by in table]
    return 100.0 * sum(shares) / len(shares)
