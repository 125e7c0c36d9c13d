#!/usr/bin/env python3
"""Step 0 of issue 33: the categorical cell on a checkout as it stands.

  chiprun --timeout 1500 -- python3 scripts/step0_cat_cell.py run
  python3 scripts/step0_cat_cell.py read        # here, from what came back

``run`` (on the chip): ``benchmark/tools/measure.py`` on
``expo255-cat.train-cat-28m`` for two plain runs (the first compiles) and
one traced; the result lines, every run's stderr and the gzipped trace land
under ``chiprun_out/measure/<cell>/``, the traced run's telemetry stream
(``route_form``, ``level_build``, ``fused_engine`` events, compile
seconds) beside them. A run that hangs is cut by the call's own timeout.

``read`` (anywhere; the trace is a file): every Pallas launch of the
steady window in order with its milliseconds (``level_pass`` and
``route_pass`` per slot count: the schedule is 1, 2, 4, ... 64 slots, then
64-slot passes), and ``benchmark/tools/phase_table.py``'s table.
"""
import glob
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "expo255-cat.train-cat-28m"
OUT = os.path.join(ROOT, "chiprun_out", "measure", CELL)


def run(seed0: int) -> int:
    rc = subprocess.call(
        [sys.executable, os.path.join(BENCH, "tools", "measure.py"),
         "--workload", CELL, "--runs", "2", "--traced", "1",
         "--seed0", str(seed0)], cwd=ROOT)
    tel = os.path.join(BENCH, ".cache", CELL, "telemetry.jsonl")
    if os.path.exists(tel):
        shutil.copy(tel, os.path.join(OUT, "telemetry.traced.jsonl"))
    return rc


def read(trace: str) -> None:
    sys.path.insert(0, BENCH)
    from harness import cells, trace_reduce
    reduced = trace_reduce.reduce(trace)
    kind = cells.load_module("kinds", "train_cat")
    window = kind.steady_window(reduced)
    dev = reduced.devices[0]
    ops = dev.ops
    mask = ops.where(trace_reduce.is_pallas) & ops.leaf \
        & (ops.start >= window.t0) & (ops.start < window.t1)
    print(f"Pallas launches of the steady window ({window.seconds:.4f} s), "
          "in order, ms:")
    for i in mask.nonzero()[0]:
        print(f"  {(ops.start[i] - window.t0) / 1e6:10.3f}  "
              f"{ops.name(i):28s} {(ops.end[i] - ops.start[i]) / 1e6:9.3f}")
    sys.stdout.flush()
    subprocess.call([sys.executable,
                     os.path.join(BENCH, "tools", "phase_table.py"),
                     "--workload", CELL, "--trace", trace], cwd=ROOT)


def main() -> None:
    what = sys.argv[1] if len(sys.argv) > 1 else ""
    if what == "run":
        sys.exit(run(int(sys.argv[2]) if len(sys.argv) > 2 else 3300000001))
    if what == "read":
        traces = sorted(glob.glob(os.path.join(OUT, "*.xplane.pb.gz")))
        read(sys.argv[2] if len(sys.argv) > 2 else traces[-1])
        return
    sys.exit(__doc__)


if __name__ == "__main__":
    main()
