"""Seconds of jax's backend phase in the first call of the training step
(``backend_compile_duration``: the cache key over the module, then the
read, deserialisation and load of the executable on a hit, or the compile
on a miss; the program's span ``first_call/load`` under ``first_call``,
which says ``cache: hit|miss``), summed over every first call that
started inside the set-up. What the executable's size decides."""
from harness import setup_spans


def read(run):
    return setup_spans.first_call_phase(run, "load")
