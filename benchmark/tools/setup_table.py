#!/usr/bin/env python3
"""Where a job's set-up and its end went, by the program's own spans:

  python3 benchmark/tools/setup_table.py --workload <cell> [--telemetry <file>] [--setup-s <s>]

Reads the telemetry stream the last run of the cell left under
``benchmark/.cache/<cell>/telemetry.jsonl`` (or ``--telemetry``) and
prints the tree of its ``setup_span`` events (a span under the span it
names as its parent and lies inside in time), each with its seconds and
its share, then under every span that holds others the part none of them
covers. The set-up is printed from the first span's start (the first
``bin``) to the last warm-up chunk's ``megastep`` event, with the warm-up
chunks' own intervals as rows; shares are of that stretch, or of
``--setup-s`` (the run's ``setup_s``, which also holds the process's
start, the back end and the data's generation: the harness's
``phases_s``). The end of the job (``finish``) follows, in shares of
itself. What ``phase_table.py`` is for the step. Needs no chip: the
stream is a file.
"""
from __future__ import annotations

import argparse
import os
import sys
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cells, monitor, setup_spans  # noqa: E402

SLACK_S = setup_spans.SLACK_S


def tree(found: list) -> list:
    """``(depth, span)`` in time order, children under their parents."""
    placed = []                 # (span, depth), in the order placed
    for s in sorted(found, key=lambda s: (s["t0"], -s["dur_s"])):
        depth = 0
        for above, d in reversed(placed):
            if above["name"] == s["parent"] \
                    and above["t0"] - SLACK_S <= s["t0"] \
                    and s["t0"] + s["dur_s"] \
                    <= above["t0"] + above["dur_s"] + SLACK_S:
                depth = d + 1
                break
        placed.append((s, depth))
    return [(d, s) for s, d in placed]


def inner_lines(inner: list, depth: int) -> list:
    """The jits folded into a phase of a first call: ``[fun_name, count,
    seconds]`` and, under a name that stands for one span, its own."""
    out = []
    for fun, n, sec, *deeper in inner:
        out.append(f"{'  ' * depth}. {fun} x{n}: {sec:.3f} s")
        out += inner_lines(deeper[0] if deeper else [], depth + 1)
    return out


def lines(rows: list, lo: float, hi: float, whole: float) -> list:
    """One line a span that starts inside [lo, hi), and after the last
    child of a span that holds others one line for what none covers."""
    out = []
    rows = [(d, s) for d, s in rows if lo <= s["t0"] < hi]
    for i, (depth, s) in enumerate(rows):
        attrs = {k: v for k, v in s.items() if k not in (
            "ts", "rank", "event", "name", "t0", "dur_s", "parent", "job",
            "inner")}
        out.append(f"{'  ' * depth}{s['name']:{34 - 2 * depth}s} "
                   f"{s['dur_s']:10.3f} s {100 * s['dur_s'] / whole:6.2f} %"
                   f"  {attrs if attrs else ''}")
        out += inner_lines(s.get("inner", []), depth + 1)
        kids = []
        for d, k in rows[i + 1:]:
            if d <= depth:
                break
            if d == depth + 1:
                kids.append((k["t0"], k["t0"] + k["dur_s"]))
        if kids:
            rest = s["dur_s"] - setup_spans.union_s(
                kids, s["t0"], s["t0"] + s["dur_s"])
            out.append(f"{'  ' * (depth + 1)}"
                       f"{'(under no span)':{32 - 2 * depth}s} "
                       f"{rest:10.3f} s {100 * rest / whole:6.2f} %")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--telemetry", default="",
                    help="a telemetry.jsonl; default: the cell's last run")
    ap.add_argument("--setup-s", type=float, default=0.0,
                    help="the run's setup_s: shares are then of it")
    ap.add_argument("--manifest", default="")
    args = ap.parse_args()
    found = cells.find_cell(cells.load_manifest(args.manifest),
                            args.workload)
    path = args.telemetry or os.path.join(BENCH, ".cache", args.workload,
                                          "telemetry.jsonl")
    run = types.SimpleNamespace(events=monitor.read_events(path),
                                traffic=found["traffic"])
    spans, end = setup_spans.spans(run), setup_spans.setup_end(run)
    if not spans or end is None:
        sys.exit(f"{path}: no setup_span events, or no warm-up chunk: "
                 "nothing to print")
    rows = tree(spans)
    start = min(s["t0"] for s in spans)
    whole = args.setup_s or end - start
    print(f"{args.workload}: set-up, {end - start:.3f} s from the first "
          f"span to the last warm-up chunk's end; shares of {whole:.3f} s")
    chunks = setup_spans.warmup_chunks(run)
    out = lines(rows, start, end, whole)
    for i, (a, b) in enumerate(chunks):
        out.append(f"{'warm-up chunk ' + str(i):34s} {b - a:10.3f} s "
                   f"{100 * (b - a) / whole:6.2f} %  (first call's end, "
                   "or the chunk before, to its megastep event)")
    print("\n".join(out))
    covered = [(s["t0"], s["t0"] + s["dur_s"])
               for s in setup_spans.leaves(spans)] + chunks
    under = setup_spans.union_s(covered, start, end)
    print(f"{'under a leaf span or a chunk':34s} {under:10.3f} s "
          f"{100 * under / whole:6.2f} %")
    print(f"{'under none':34s} {end - start - under:10.3f} s "
          f"{100 * (end - start - under) / whole:6.2f} %")
    finish = [s for s in spans if s["name"] == "finish"]
    if finish:
        f = finish[-1]
        print(f"\nthe end of the job; shares of {f['dur_s']:.3f} s")
        print("\n".join(lines(rows, f["t0"], f["t0"] + f["dur_s"] + SLACK_S,
                              f["dur_s"] or 1.0)))


if __name__ == "__main__":
    main()
