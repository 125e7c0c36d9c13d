"""The nine readers that open ``setup_s`` end to end on the CPU: the train
kind and the GOSS kind (two step bodies inside the warm-up) at a tiny
size through a rehearsal manifest of their own
(``rehearsal-setup.json``), the spans against the outside metrics they
open, and the table ``tools/setup_table.py`` prints from the stream."""
import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT
from harness import cells, monitor
from test_layers_setup import NINE
from test_rehearsal import LINE_KEYS, run_cell

MANIFEST = os.path.join(BENCH, "tests", "rehearsal-setup.json")
OUTSIDE = {"entry.tail_s", "startup.step_first_call_s", "data.bin_s",
           "data.upload_pack_s"}


@pytest.fixture(scope="module", params=["rehearsal-setup.train",
                                        "rehearsal-setup-goss.train"])
def traced(request):
    proc = run_cell(request.param, 1, manifest=MANIFEST, seed=2 ** 31 + 37)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    found = cells.find_cell(cells.load_manifest(MANIFEST), request.param)
    events = monitor.read_events(os.path.join(
        BENCH, ".cache", request.param, "telemetry.jsonl"))
    run = types.SimpleNamespace(events=events, traffic=found["traffic"])
    return request.param, last, run


def read(name, run):
    return cells.load_module("layers", name).read(run)


def test_the_cell_produces_all_nine(traced):
    cell, last, run = traced
    assert LINE_KEYS <= set(last)
    assert last["correct"] is True, last["problems"]
    assert last["metrics"] == {}            # no device metric off the chip
    assert set(last["rehearsal"]["produced"]) == set(NINE) | OUTSIDE
    assert read("entry.setup_coverage", run) >= 90
    bodies = 2 if "goss" in cell else 1
    calls = [s for s in monitor.of_kind(run.events, "setup_span")
             if s["name"] == "first_call"]
    assert len(calls) == bodies
    loads = [s for s in monitor.of_kind(run.events, "setup_span")
             if s["name"] == "first_call/load" and s["parent"] == "first_call"]
    assert len(loads) >= bodies


def test_the_spans_agree_with_what_they_open(traced):
    """The spans lie inside the outside metrics of the same run (the
    harness's own clock around the same calls)."""
    _, last, run = traced
    phases = last["phases_s"]
    spans = monitor.of_kind(run.events, "setup_span")
    built = monitor.of_kind(run.events, "compile_executable")
    first = min((s for s in spans if s["name"] == "first_call"),
                key=lambda s: s["t0"])
    assert first["dur_s"] == pytest.approx(built[0]["compile_ms"] / 1e3,
                                           abs=0.1)
    three = sum(read(f"startup.step_{p}_s", run)
                for p in ("trace", "lower", "load"))
    assert three <= sum(s["dur_s"] for s in spans
                        if s["name"] == "first_call")
    assert read("data.bin_rows_s", run) <= phases["bin"]
    train = [s for s in spans if s["name"] == "train"][-1]
    upload_pack = built[0]["ts"] - built[0]["compile_ms"] / 1e3 - train["t0"]
    assert read("data.upload_s", run) + read("data.pack_s", run) \
        <= upload_pack
    mega = monitor.of_kind(run.events, "megastep")
    tail = train["t0"] + train["dur_s"] - mega[-1]["ts"]
    assert read("entry.tail_drain_s", run) \
        + read("entry.tail_finalize_s", run) == pytest.approx(tail, abs=0.2)


def test_the_table_from_the_stream(traced):
    cell, _, _ = traced
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "setup_table.py"),
         "--workload", cell, "--manifest", MANIFEST],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    for name in ("bin/rows", "train/booster_init", "init/upload",
                 "init/pack", "first_call/trace", "first_call/load",
                 "warm-up chunk 0", "under none", "finish/drain",
                 "(under no span)"):
        assert name in out, name
    share = float(out.split("under a leaf span or a chunk")[1].split()[2])
    assert share >= 90


def test_the_manifest_finds_its_files_and_the_real_one_lists_the_nine():
    manifest = cells.load_manifest(MANIFEST)
    assert manifest["rehearsal"] is True
    for cell in manifest["workloads"]:
        cells.find_cell(manifest, cell["name"])
        for m in cells.metrics_of(manifest, "per_layer", cell["name"]):
            assert hasattr(cells.load_module("layers", m["name"]), "read")
    real = {m["name"]: m for m in cells.load_manifest()["per_layer"]}
    for name in NINE:
        m = real[name]
        assert (m["source"], m["moves"]) == ("program_span", "setup_s")
        assert "workloads" not in m         # every cell has a set-up
