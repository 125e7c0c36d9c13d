"""Device busy time of the objective's gradients inside the scan (every
operation under ``lgbm.gradients``: for a ranking objective the window
gather, the per-query sorts, the pair planes and the way back to row
order) over device busy time, in per cent, mean over the chips."""
from harness import trace_rank


def read(run):
    return trace_rank.share(run, lambda stage: True)
