"""Seconds the device took to lay the uploaded matrices out for the
kernels: the program's spans ``init/pack`` (transpose, cast, pad),
``init/reshard`` (the placement over the mesh, four chips) and
``valid/pack`` (each validation set's passenger matrix), each closed
when the device has finished."""
from harness import setup_spans


def read(run):
    return setup_spans.total(run, "init/pack", "init/reshard", "valid/pack")
