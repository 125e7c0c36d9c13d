#!/usr/bin/env python3
"""Where a traced chunk's device time went, by the program's own phases:

  python3 benchmark/tools/phase_table.py --workload <cell> [--trace <file>]

Reads the trace the last ``--trace 1`` run of the cell left under
``benchmark/.cache/<cell>/trace`` (or ``--trace``: an ``.xplane.pb``,
gzipped or not), takes the same steady window as the run did (one whole
run of the training step and the gap after it), and prints per chip and
per boosting iteration: phase x (Pallas ms, XLA ms, launches), the three
longest operations of each phase with the end of their ``op_name`` and
their ``source_file:line``, the sums that ``PERF.md`` section 3's
identity is checked with, and for every chunk boundary of the trace the
device's gap with the program's sections inside it. Needs no
chip: the trace is a file.
"""
from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cells, trace_phases, trace_reduce  # noqa: E402


def chip_table(window, dev, named: dict, iters: int, top: int) -> list:
    """Lines of one chip's table."""
    busy = window.busy_ns(dev)
    rows = {}                   # phase -> [pallas ns, xla ns, launches, ops]
    for phase, pallas, name, ns, launches, known in trace_phases.phased_ops(
            window, dev, named):
        row = rows.setdefault(phase, [0, 0, 0, []])
        row[0 if pallas else 1] += ns
        row[2] += launches
        row[3].append((ns, launches, name, known))
    per_iter = 1e6 * iters
    out = [f"{dev.name}: window {window.seconds:.4f} s, busy "
           f"{100.0 * busy / (window.t1 - window.t0):.2f} %, "
           f"{iters} iterations; ms per iteration",
           f"{'phase':22s} {'Pallas ms':>10s} {'XLA ms':>10s} "
           f"{'% of busy':>9s} {'launches':>8s}"]
    for phase, (p_ns, x_ns, launches, ops) in sorted(
            rows.items(), key=lambda kv: -(kv[1][0] + kv[1][1])):
        out.append(f"{phase:22s} {p_ns / per_iter:10.3f} "
                   f"{x_ns / per_iter:10.3f} "
                   f"{100.0 * (p_ns + x_ns) / busy:9.2f} "
                   f"{launches / iters:8.1f}")
        for ns, n, name, known in sorted(ops, key=lambda o: -o[0])[:top]:
            tail = "/".join(known.op_name.split("/")[-3:]) if known else "-"
            where = known.source if known and known.source else "-"
            out.append(f"    {name:34s} {ns / per_iter:9.3f} ms "
                       f"x{n / iters:5.1f}  {tail}  {where}")

    def xla_share(tops):
        return 100.0 * sum(r[1] for ph, r in rows.items()
                           if trace_phases.top_phase(ph) in tops) / busy

    parts = {
        "kernels.pallas_share":
            100.0 * sum(r[0] for r in rows.values()) / busy,
        "grower.glue_share": xla_share(("grow",)),
        "update (XLA part)": xla_share(trace_phases.UPDATE),
        "eval (XLA part)": xla_share(trace_phases.EVAL),
        "unscoped (XLA part)": xla_share((trace_phases.UNSCOPED,)),
    }
    out.append("identity: " + " + ".join(
        f"{k} {v:.3f}" for k, v in parts.items())
        + f" = {sum(parts.values()):.3f} (100 = all busy time)")
    return out


def boundary_table(reduced) -> list:
    """Every chunk boundary of the trace: the gap on the first chip
    between two runs of the step, the program's sections inside it, and
    its longest idle stretches by what the host was doing."""
    dev = reduced.devices[0]
    out = ["chunk boundaries (first chip): device gap between two runs of "
           "the step, and the program thread's sections inside it, ms"]
    for t0, t1 in trace_phases.chunk_boundaries(reduced):
        found = trace_phases.host_section_ns(
            reduced, t0, t1, lambda name: "::" in name)
        out.append(f"  gap {(t1 - t0) / 1e6:9.3f}: " + ", ".join(
            f"{name} {ns / 1e6:.3f}" for name, ns in sorted(
                found.items(), key=lambda kv: -kv[1])))
        inside = reduced.window(t0, t1)
        longest = sorted(inside.gaps(dev), key=lambda g: g[0] - g[1])[:3]
        out.append("      idle: " + ", ".join(
            f"{(y - x) / 1e6:.3f} under {inside.host_label(x, y)}"
            for x, y in longest))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", default="",
                    help="an .xplane.pb[.gz]; default: the cell's last trace")
    ap.add_argument("--manifest", default="")
    ap.add_argument("--top", type=int, default=3)
    args = ap.parse_args()
    found = cells.find_cell(cells.load_manifest(args.manifest),
                            args.workload)
    iters = int(found["traffic"]["chunk_iterations"])
    path = args.trace or trace_reduce.find_xplane(
        os.path.join(cells.BENCH, ".cache", args.workload, "trace"))
    reduced = trace_reduce.reduce(path)
    window = cells.load_module("kinds", found["traffic"]["kind"]) \
        .steady_window(reduced)
    named = trace_phases.names(path)
    print(f"{args.workload}: {path}")
    for dev in reduced.devices:
        print("\n".join(chip_table(window, dev, named.get(dev.name, {}),
                                   iters, args.top)))
        print()
    print("\n".join(boundary_table(reduced)))


if __name__ == "__main__":
    main()
