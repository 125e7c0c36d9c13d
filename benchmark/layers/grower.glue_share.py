"""Device busy time of the operations under ``lgbm.grow`` that are NOT
Pallas kernels (slot and route tables, histogram decode and pool,
split search, tree bookkeeping, the level loop's copies) over device busy
time, in per cent, mean over the chips."""
from harness import trace_phases


def read(run):
    return trace_phases.share(
        run, lambda phase, pallas:
        trace_phases.top_phase(phase) == "grow" and not pallas)
