"""Seconds the binned matrices took from the host to the device: the
program's spans ``init/upload`` (training set) and ``valid/upload`` (each
validation set), each closed when the copy has landed."""
from harness import setup_spans


def read(run):
    return setup_spans.total(run, "init/upload", "valid/upload")
