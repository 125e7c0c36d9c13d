"""The compaction kernel's share of its roofline, in per cent: the least
time the chip's memory could take to read every row's destination, bins
and channels and write the kept columns (``harness/work_goss.py``), once
per tree grown in the traced window, over the time the ``compact_rows``
launches took, on the slowest chip. Memory-bound by construction; None
where no such kernel ran."""
from harness import trace_reduce, work, work_goss

KERNEL = "compact_rows"


def read(run):
    w, f = run.window, run.facts
    if w is None or "window_trees" not in f or "rows_total" not in f:
        return None
    took = max(w.busy_ns(d, d.ops.where(
        lambda op: trace_reduce.is_pallas(op)
        and trace_reduce.kernel_of(op) == KERNEL)) for d in w.reduced.devices)
    if not took:
        return None
    peak = work.peaks(run.devices[0].device_kind)
    nbytes = len(f["window_trees"]) * work_goss.compact_bytes(
        f["rows_total"], f["rows"], f["features"])
    return 100.0 * (nbytes / peak["hbm_bytes_per_s"]) / (took / 1e9)
