#!/usr/bin/env python3
"""Measure a cell the way the driver does, in one call on the chip:

  chiprun -- python3 benchmark/tools/measure.py --workload <name> --runs 12 --traced 1

Each run is a new process of the benchmark's own command with another
``--seed``, one after the other (this process never touches JAX, so the
chip is free for each). The runs are split into two sets in order; per
end-to-end metric it prints each set's median and spread (distance
between the quartiles over the median), the wider spread, and how far
the second median lies from the first. The first run of a checkout
compiles: its ``setup_s`` is listed apart and left out of the sets'
``setup_s``. Results, logs and (with ``--traced``) the raw traces go to
``--out`` (``chiprun_out/measure/<workload>/``). It stops at the first run that
fails, and exits non-zero if any run was not correct.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def spread(values: list) -> float:
    """Distance between the quartiles over the median."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def one_run(manifest: dict, workload: str, seed: int, trace: int,
            log_path: str) -> dict:
    cmd = manifest["command"] + [
        "--workload", workload, "--seed", str(seed), "--seconds",
        str(manifest["run_seconds"]), "--trace", str(trace)]
    t0 = time.time()
    with open(log_path, "w") as log:
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                              stderr=log, text=True)
    lines = proc.stdout.strip().splitlines()
    out = {"rc": proc.returncode, "wall_s": time.time() - t0, "seed": seed,
           "trace": trace}
    if proc.returncode == 0 and lines:
        out["line"] = json.loads(lines[-1])
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=12)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs after the plain ones")
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--out", default="",
                    help="default <checkout>/chiprun_out/measure/<workload>")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    out_dir = args.out or os.path.join(ROOT, "chiprun_out", "measure",
                                       args.workload)
    os.makedirs(out_dir, exist_ok=True)
    results = []
    plan = [0] * args.runs + [1] * args.traced
    for i, trace in enumerate(plan):
        res = one_run(manifest, args.workload, args.seed0 + i, trace,
                      os.path.join(out_dir, f"run{i:02d}.stderr"))
        results.append(res)
        with open(os.path.join(out_dir, "results.jsonl"), "a") as fh:
            fh.write(json.dumps(res) + "\n")
        line = res.get("line", {})
        print(f"run {i} trace={trace} rc={res['rc']} wall={res['wall_s']:.1f}s "
              f"correct={line.get('correct')} "
              + json.dumps({k: v["value"] for k, v in
                            line.get("metrics", {}).items()}), flush=True)
        if trace:
            for pb in glob.glob(os.path.join(
                    BENCH, ".cache", args.workload, "trace", "plugins",
                    "profile", "*", "*.xplane.pb")):
                with open(pb, "rb") as src, gzip.open(os.path.join(
                        out_dir, f"run{i:02d}.xplane.pb.gz"), "wb") as dst:
                    shutil.copyfileobj(src, dst)
        if res["rc"] != 0 or not line:
            print(f"stopping: run {i} failed; see {out_dir}/run{i:02d}.stderr",
                  flush=True)
            with open(os.path.join(out_dir, f"run{i:02d}.stderr")) as fh:
                sys.stderr.write(fh.read()[-6000:])
            sys.exit(1)
        if not line["correct"]:
            print(f"run {i} NOT CORRECT: {line.get('problems')}", flush=True)
    plain = [r["line"] for r in results if not r["trace"]]
    for name in (m["name"] for m in manifest["end_to_end"]):
        vals = [r["metrics"][name]["value"] for r in plain
                if name in r["metrics"]]
        if not vals:
            continue
        first = None
        if name == "setup_s":
            first, vals = vals[0], vals[1:]
        sets = [s for s in (vals[:len(vals) // 2], vals[len(vals) // 2:])
                if s]
        meds = [statistics.median(s) for s in sets]
        print(json.dumps({
            "metric": name, "first_run": first, "values": vals,
            "medians": meds, "spreads": [spread(s) for s in sets],
            "second_vs_first": (meds[1] / meds[0] - 1.0
                                if len(meds) == 2 else None)}), flush=True)
    for r in results:
        if r["trace"]:
            print(json.dumps({"traced": r["line"]["metrics"],
                              "device": r["line"]["device"],
                              "breakdown": r["line"].get("breakdown")}),
                  flush=True)
    if not all(r["line"]["correct"] for r in results):
        sys.exit(1)


if __name__ == "__main__":
    main()
