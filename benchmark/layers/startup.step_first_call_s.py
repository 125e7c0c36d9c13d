"""Seconds the first call of the training step took on the host (the
``compile_executable`` event's ``compile_ms``): tracing the scan, then
compiling it or loading it from the persistent cache, then the dispatch."""


def read(run):
    return run.facts.get("step_first_call_s")
