"""Rows a tree's ``level_pass`` launches stream over the training rows, in
per cent, over the measured chunks: the program's own count
(``rows_streamed`` and ``trees`` of each ``megastep`` event, which also
feed the counters ``level.rows_streamed`` and ``level.trees``). 30.0 when a
``top_rate 0.2`` / ``other_rate 0.1`` sample is compacted before the level
passes, 100 when it is only a weight vector. An exact count; None for a
program that does not say."""


def read(run):
    f = run.facts
    streamed = f.get("streamed")
    if not streamed or not f.get("rows_total"):
        return None
    rows = sum(r for r, _ in streamed)
    trees = sum(t for _, t in streamed)
    return 100.0 * rows / trees / f["rows_total"]
