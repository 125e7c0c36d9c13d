"""Device busy time of evaluation inside the scan (the validation
scores' tree walk, the traced metrics with the validation sort, the
early-stop latch) over device busy time, in per cent, mean over the
chips."""
from harness import trace_phases


def read(run):
    return trace_phases.share(
        run, lambda phase, pallas:
        trace_phases.top_phase(phase) in trace_phases.EVAL)
