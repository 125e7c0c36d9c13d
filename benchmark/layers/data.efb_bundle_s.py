"""Seconds the sparse sets' exclusive feature bundling took at ingestion:
the program's spans ``bin/bundle/find`` (the greedy over the sample,
each placement checked on every row) and ``bin/bundle/encode`` (the
bundle columns from the CSC columns, the training set's and the
validation set's), over the job's sets. The part of ``data.bin_s`` that
EFB adds; nothing where the program has no such span."""
from harness import setup_spans


def read(run):
    return setup_spans.total(run, "bin/bundle/find", "bin/bundle/encode")
