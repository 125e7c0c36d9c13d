"""Seconds inside ``TpuDataset.bin_rows`` over the job's sets (the
program's span ``bin/rows``): the part of ``data.bin_s`` that grows with
the rows, beside the sample and the mappers."""
from harness import setup_spans


def read(run):
    return setup_spans.total(run, "bin/rows")
