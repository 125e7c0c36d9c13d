"""Device busy time under any of the program's ``lgbm.*`` phase scopes
over device busy time, in per cent, mean over the chips. What is left is
compiler-made (copies, ``reduce-window``s, asynchronous ``-done``s) and
carries no name."""
from harness import trace_phases


def read(run):
    return trace_phases.share(
        run, lambda phase, pallas: phase != trace_phases.UNSCOPED)
