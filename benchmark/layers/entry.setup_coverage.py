"""Share, in per cent, of the interval from the start of the program's
``train`` span to the end of the set-up (the last warm-up chunk's
``megastep`` event) that lies under a LEAF ``setup_span`` or inside a
warm-up chunk's own interval (from its step's first call to its
``megastep`` event): what of ``setup_s`` inside ``lgb.train`` the
program's spans account for."""
from harness import setup_spans


def read(run):
    found, end = setup_spans.spans(run), setup_spans.setup_end(run)
    start = [s["t0"] for s in found if s["name"] == "train"]
    if not start or end is None or end <= start[-1]:
        return None
    covered = [(s["t0"], s["t0"] + s["dur_s"])
               for s in setup_spans.leaves(found)]
    covered += setup_spans.warmup_chunks(run)
    return 100.0 * setup_spans.union_s(covered, start[-1], end) \
        / (end - start[-1])
