"""Device busy time of the per-row update around the grower (gradients
from scores, the gradient/hessian pack, the train-score update with its
lookup kernel, the early-stop freeze of the score carries) over device
busy time, in per cent, mean over the chips."""
from harness import trace_phases


def read(run):
    return trace_phases.share(
        run, lambda phase, pallas:
        trace_phases.top_phase(phase) in trace_phases.UPDATE)
