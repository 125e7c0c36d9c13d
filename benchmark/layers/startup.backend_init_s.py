"""Seconds the first ``jax.devices()`` took: backend start."""


def read(run):
    return run.phases.get("backend_init")
