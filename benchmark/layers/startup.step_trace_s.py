"""Seconds of Python tracing in the first call of the training step (jax's
``jaxpr_trace_duration`` of the step's own jit, as the program's span
``first_call/trace`` under ``first_call``), summed over every first call
that started inside the set-up: two in a job with two step bodies. What a
grower with a loop in place of unrolled levels would cut."""
from harness import setup_spans


def read(run):
    return setup_spans.first_call_phase(run, "trace")
