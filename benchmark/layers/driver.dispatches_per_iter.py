"""The program's ``train.dispatches`` counter over the iterations of the
job: an exact count (1 / chunk_iterations on the megastep)."""


def read(run):
    f = run.facts
    if not f.get("dispatches") or not f.get("iterations"):
        return None
    return f["dispatches"] / f["iterations"]
