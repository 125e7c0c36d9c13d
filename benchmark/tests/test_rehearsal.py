"""The train kind end to end on the CPU at a tiny size, through the rehearsal
manifest: cells that are in no ``workloads`` entry of BENCHMARK.json and
were added the way benchmark/README.md describes, by files alone."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

MANIFEST = os.path.join(BENCH, "tests", "rehearsal.json")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload, trace, manifest=MANIFEST, seed=7):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", "30", "--trace",
           str(trace)]
    if manifest:
        cmd += ["--manifest", manifest]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


TRAIN_LAYERS = {"data.bin_s", "data.upload_pack_s",
                "driver.dispatches_per_iter", "entry.tail_s",
                "startup.backend_init_s", "startup.cache_hit",
                "startup.step_first_call_s"}


@pytest.mark.parametrize("workload,trace,produced", [
    ("rehearsal.train", 0, {"setup_s", "train_s_per_iter"}),
    ("rehearsal.train", 1, TRAIN_LAYERS),
    ("rehearsal-dp4.train", 0, {"setup_s", "train_s_per_iter"}),
    ("rehearsal-dp4.train", 1, TRAIN_LAYERS),
])
def test_cell_runs_on_the_cpu_and_names_it(workload, trace, produced):
    proc = run_cell(workload, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(last)
    assert "breakdown" not in last          # no device trace on the CPU
    assert last["correct"] is True, last["problems"]
    assert last["failed"] == 0 and last["attempted"] >= 3
    # the CPU is named and no device metric is printed under any name
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    assert last["metrics"] == {}
    assert set(last["rehearsal"]["produced"]) == produced
    if "driver.dispatches_per_iter" in produced:
        # chunks of 2 iterations: an exact count, the same on any backend
        assert last["rehearsal"]["counts"][
            "driver.dispatches_per_iter"] == 0.5


def test_a_real_cell_refuses_the_cpu():
    """No TPU: non-zero exit, nothing on standard output."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        cell = json.load(fh)["workloads"][0]["name"]
    proc = run_cell(cell, 0, manifest="")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
