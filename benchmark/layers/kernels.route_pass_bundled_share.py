"""``route_pass``'s share of device busy time in a bundled job, in per
cent: the reader of ``kernels.route_pass_share`` (loaded from its file,
not copied), listed for the one-hot cell, where the bins form decodes a
bundle value in every routing pass and the table form builds the whole
one-hot of the bundle columns."""
from harness import cells


def read(run):
    return cells.load_module("layers", "kernels.route_pass_share").read(run)
