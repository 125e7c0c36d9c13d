"""Seconds between the end of the last chunk (its ``megastep`` event) and
the return of ``lgb.train``: the entry point's own epilogue."""


def read(run):
    f = run.facts
    if "t_train1" not in f or "t_last_chunk" not in f:
        return None
    return f["t_train1"] - f["t_last_chunk"]
