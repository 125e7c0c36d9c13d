"""The two start-up decisions (utils/platform.py) and the entry scripts'
refusal to run on a device nobody asked for."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import log, platform
from lightgbm_tpu.utils.log import LightGBMError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def requested_platforms():
    """Set jax_platforms for one test (the backends are up already, so
    this changes only what the process says it asked for)."""
    before = jax.config.jax_platforms

    def request(value):
        jax.config.update("jax_platforms", value)
    yield request
    jax.config.update("jax_platforms", before)


def test_tpu_backend_means_compiled(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.on_tpu() is True


def test_cpu_by_name_is_the_test_mode(requested_platforms):
    # what conftest.py (and JAX_PLATFORMS=cpu) select
    assert platform.on_tpu() is False
    requested_platforms("cpu,tpu")
    assert platform.on_tpu() is False


@pytest.mark.parametrize("requested", ["", "tpu,cpu"])
def test_fallback_to_cpu_is_fatal_and_named(requested_platforms, requested):
    # no request at all, or a TPU request that JAX quietly served from
    # the CPU: both used to train on the CPU and exit 0
    requested_platforms(requested)
    with pytest.raises(LightGBMError, match="found platform 'cpu'"):
        platform.on_tpu()
    X = np.random.RandomState(0).rand(200, 4)
    with pytest.raises(LightGBMError, match="found platform 'cpu'"):
        lgb.train({"objective": "binary", "verbose": -1},
                  lgb.Dataset(X, label=(X[:, 0] > 0.5).astype(float)),
                  num_boost_round=1)


def test_other_accelerator_is_fatal(monkeypatch, requested_platforms):
    requested_platforms("")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(LightGBMError, match="found platform 'gpu'"):
        platform.on_tpu()


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_set_nothing_set_in_code(monkeypatch, tmp_path,
                                           cache_config):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    lines = []
    level = log.get_log_level()
    log.set_log_level(log.LogLevel.INFO)
    log.register_logger(lines.append)
    try:
        assert platform.compilation_cache_dir() == env_dir
        assert platform.compilation_cache_dir(
            str(tmp_path / "from_key")) == env_dir
    finally:
        log.register_logger(None)
        log.set_log_level(level)
    assert jax.config.jax_compilation_cache_dir == before
    assert len(lines) == 1 and "yields to" in lines[0] \
        and "from_key" in lines[0]


def test_cache_env_unset_fixed_in_checkout_path(monkeypatch, tmp_path,
                                                cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert platform.compilation_cache_dir() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    key_dir = str(tmp_path / "from_key")
    assert platform.compilation_cache_dir(key_dir) == key_dir
    assert jax.config.jax_compilation_cache_dir == key_dir


def _run_script(name, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(REPO, name), *args],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)


def test_chip_smoke_refuses_the_cpu():
    r = _run_script("chip_smoke.py")
    assert r.returncode != 0
    assert "JAX found platform 'cpu'" in r.stderr
    assert r.stdout.strip() == ""          # no result line


def test_chip_smoke_verdict_is_the_last_thing_printed():
    """The driver parses the last stdout line and refuses any key beyond
    ok / device{platform, kind, count}."""
    import ast
    import importlib.util
    path = os.path.join(REPO, "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    line = smoke.verdict({"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1, "extra": "dropped"})
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    with open(path) as fh:
        tree = ast.parse(fh.read())
    main = next(n for n in tree.body
                if isinstance(n, ast.FunctionDef) and n.name == "main")
    assert ast.unparse(main.body[-1]) == "print(verdict(device), flush=True)"


def test_bench_chip_mode_exits_nonzero_without_a_tpu(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_TRAJECTORY", str(tmp_path / "traj.jsonl"))
    r = _run_script("bench.py")
    assert r.returncode != 0
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["error"] == "no_tpu:found_cpu" and rec["value"] is None
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1


def test_launcher_refuses_several_chip_workers_on_one_host(tmp_path):
    from lightgbm_tpu.parallel import train_distributed
    with pytest.raises(LightGBMError, match="one process"):
        train_distributed({"objective": "binary"}, str(tmp_path / "x.csv"),
                          num_processes=2, use_cpu=False)
