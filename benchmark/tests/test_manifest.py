"""BENCHMARK.json against the contract's mechanical rules, and against
the files it names: every cell, configuration, mix, kind and per-layer
reader is found by name."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT
from harness import cells

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["BENCHMARK.json",
                                        "benchmark/tests/rehearsal.json"])
def manifest(request):
    with open(os.path.join(ROOT, request.param)) as fh:
        return json.load(fh)


def test_keys_and_limits(manifest):
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(manifest) - {"rehearsal"} == keys
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["configs"]) <= 24
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    # a full check of 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200


def test_names_and_whys(manifest):
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in manifest[group]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200
               for x in manifest["configs"] + manifest["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_metrics(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for m in manifest["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m
    for w in manifest["workloads"]:
        mine = cells.metrics_of(manifest, "end_to_end", w["name"])
        assert {"setup_s"} < {m["name"] for m in mine}
        layers = cells.metrics_of(manifest, "per_layer", w["name"])
        assert layers
        # a per-layer metric is reported only where what it moves is
        assert {m["moves"] for m in layers} <= {m["name"] for m in mine}


def test_every_name_finds_its_file(manifest):
    for w in manifest["workloads"]:
        found = cells.find_cell(manifest, w["name"])
        assert found["config"]["name"] == w["config"]
        assert found["config"]["chips"] == w["chips"]
        kind = found["traffic"]["kind"]
        assert hasattr(cells.load_module("kinds", kind), "run")
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        assert sorted(c["reduced"]) == sorted(
            cells.load_json(os.path.join(ROOT, c["file"]))["reduced"])
    for m in manifest["per_layer"]:
        assert hasattr(cells.load_module("layers", m["name"]), "read")


def test_every_file_of_the_benchmark_has_a_plain_name():
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, _, files in os.walk(BENCH):
        if ".cache" in folder or "__pycache__" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert plain.match(rel) and len(rel) <= 200, rel
