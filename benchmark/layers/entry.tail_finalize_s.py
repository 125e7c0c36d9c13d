"""Seconds of the job's end that are not the drain: the program's span
``finish`` (from the return of the last update to the return of
``lgb.train``) less its ``finish/drain`` children: the score profile,
SLO step, cost flush, summary, run report, trace export, flush and the
callbacks' ``finalize``."""
from harness import setup_spans


def read(run):
    whole = setup_spans.total(run, "finish")
    if whole is None:
        return None
    return whole - (setup_spans.total(run, "finish/drain",
                                      parent="finish") or 0.0)
