"""Seconds from the call of ``lgb.train`` to its first dispatch (the
``compile_executable`` event's ``ts`` minus its ``compile_ms``): booster
construction, upload of the bin matrix, the transposed pack."""


def read(run):
    f = run.facts
    if "t_dispatch0" not in f:
        return None
    return f["t_dispatch0"] - f["t_train0"]
