"""Seconds of the sparse path's own binning over the job's sets: the
program's spans ``bin/sparse/csc`` (the CSR turned into columns) and
``bin/sparse/sample`` (the bin mappers from the sampled rows); nothing
where the program has no such span."""
from harness import setup_spans


def read(run):
    return setup_spans.total(run, "bin/sparse/csc", "bin/sparse/sample")
