"""Device nanoseconds under ``lgbm.gradients`` per boosting iteration
over the pairs an iteration forms (the program's exact counter
``rank.pairs_per_iter``: the sum over the queries of |{(i, j): i < j, i <
lambdarank_truncation_level}|), on the slowest chip."""
from harness import trace_rank


def read(run):
    pairs = run.facts.get("rank_pairs_per_iter")
    table = trace_rank.busy_by_stage(run)
    if table is None or not pairs:
        return None
    took = max(sum(by.values()) for _, by in table)
    return took / run.facts["chunk_iterations"] / pairs
