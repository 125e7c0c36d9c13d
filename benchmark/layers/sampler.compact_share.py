"""Device busy time under ``lgbm.sample`` > ``compact`` (the in-bag rows of
the bin matrix and of the gradient channels moved to the front of the
compact matrix a sampled tree is grown on, with the prefix sum of the mask
that says where each goes) over device busy time, in per cent, mean over
the chips. None where the program has no such scope."""
from harness import cells

STAGES = ("compact",)


def read(run):
    return cells.load_module("layers", "sampler.select_share") \
        .share(run, STAGES)
