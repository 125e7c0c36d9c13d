"""The level kernel's share of its roofline, in per cent: the least time
the chip could take for the one-hot formulation's work on the traced
trees (operations and bytes from shapes, benchmark/harness/work.py; the
larger of operations over the bf16 peak and bytes over the HBM peak) over
the time the ``level_pass`` launches took, on the slowest chip.
Compute-bound at these shapes."""
from harness import trace_reduce, work

KERNEL = "level_pass"


def read(run):
    w, f = run.window, run.facts
    if w is None or "window_trees" not in f:
        return None
    peak = work.peaks(run.devices[0].device_kind)
    rows = f["rows"] / f["chips"]               # each chip's shard
    ops = sum(work.onehot_ops(rows, f["features"], f["max_bin"],
                              f["tree_leaves"][t])
              for t in f["window_trees"])
    nbytes = sum(work.level_bytes(rows, f["features"], f["tree_levels"][t])
                 for t in f["window_trees"])
    least_s, _ = work.roofline_seconds(ops, nbytes, peak)
    took = max(w.busy_ns(d, d.ops.where(
        lambda op: trace_reduce.is_pallas(op)
        and trace_reduce.kernel_of(op) == KERNEL)) for d in w.reduced.devices)
    return 100.0 * least_s / (took / 1e9) if took else None
