"""Seconds of lowering in the first call of the training step (jax's
``jaxpr_to_mlir_module_duration``: jaxpr to MLIR, each Pallas kernel's
Mosaic lowering with it; the program's span ``first_call/lower`` under
``first_call``), summed over every first call that started inside the
set-up."""
from harness import setup_spans


def read(run):
    return setup_spans.first_call_phase(run, "lower")
