"""Device busy time under ``lgbm.gradients/rank_sort`` (the window gather
of the scores into the length buckets and the sort of every query along
its bucket's short axis) over device busy time, in per cent, mean over
the chips."""
from harness import trace_rank


def read(run):
    return trace_rank.share(run, lambda stage: stage == "rank_sort")
