"""Seconds of the job's end spent draining (the program's spans
``finish/drain``: the disarm of the megastep and the last
``drain_pending``)."""
from harness import setup_spans


def read(run):
    return setup_spans.total(run, "finish/drain")
