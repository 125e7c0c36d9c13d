"""Multi-process training launcher — the orchestration analog of the
reference's Dask integration, plus supervised fault recovery.

The reference's ``dask.py`` finds open ports, builds the ``machines``
string, runs one local fit per worker, and returns rank 0's booster
(ref: python-package/lightgbm/dask.py:67-135 port negotiation, :166
``_train_part``, :392 ``_train``). On the JAX runtime the transport
negotiation collapses to ``jax.distributed.initialize`` against one
coordinator address; this module supplies the remaining orchestration:
spawn N processes, give each its rank, let each load its shard of the
data file (the loader reads per-rank row slices and allgathers the
binning sample), train ONE model jointly (``tree_learner=data`` over
the global mesh — parallel/multiproc.py), and hand back rank 0's
booster.

**Elastic recovery** (docs/Reliability.md): XLA collectives make one
rank's crash fatal to the cohort, so the launcher supervises — it polls
the workers, and when any rank dies it kills the rest, selects the
newest checkpoint that is complete and hash-consistent across ALL
ranks (``resilience.checkpoint.select_checkpoint``), and respawns the
cohort resuming from it, with capped retries and exponential backoff.
With ``checkpoint_period=N`` the lost work is bounded by N iterations;
the final model is bit-identical to an uninterrupted run.

Single-host: N local processes over gloo collectives on the CPU. On a
TPU host ONE process drives every local chip (``tree_learner=data``
over ``jax.devices()`` — a chip belongs to one process at a time), so
the launcher refuses ``use_cpu=False`` with more than one local worker;
multi-host works by running one worker per host with
``coordinator_address`` pointing at host 0 — the exact shape of the
reference's machine-list deployments.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, Optional

from ..resilience.checkpoint import select_checkpoint
from ..resilience.faults import FAULT_STATE_ENV
from ..utils import log

_WORKER = """
import json, os, sys
cfg = json.load(open(sys.argv[1]))
import jax
if cfg["env"].get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=cfg["coordinator"],
    num_processes=cfg["num_processes"], process_id=cfg["rank"])
import lightgbm_tpu as lgb

ds = lgb.Dataset(cfg["data"], params=cfg["dataset_params"])
bst = lgb.train(cfg["params"], ds,
                num_boost_round=cfg["num_boost_round"],
                resume_from=cfg.get("resume") or None)
if jax.process_index() == 0:
    with open(cfg["out"], "w") as fh:
        fh.write(bst.model_to_string(num_iteration=-1))
"""


def _free_port() -> int:
    # NOTE: inherently racy (the socket closes before the coordinator
    # rebinds); SO_REUSEADDR narrows the window. Contended environments
    # should pass coordinator_address explicitly.
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_cohort(td, script, params, data_path, num_processes,
                  num_boost_round, dataset_params, out, coord,
                  devices_per_process, use_cpu, pkg_root, resume,
                  attempt, extra_env):
    procs, logs = [], []
    for rank in range(num_processes):
        cfg = {"coordinator": coord, "num_processes": num_processes,
               "rank": rank, "data": str(data_path),
               "params": params, "num_boost_round": num_boost_round,
               "dataset_params": dict(dataset_params or {}),
               "out": out, "resume": resume or "",
               "env": {"JAX_PLATFORMS": "cpu"} if use_cpu else {}}
        cfg_path = os.path.join(td, f"cfg{rank}_a{attempt}.json")
        with open(cfg_path, "w") as fh:
            json.dump(cfg, fh)
        env = dict(os.environ)
        env.update(extra_env or {})
        env.pop("XLA_FLAGS", None)   # inherited flags never apply
        if devices_per_process > 0:
            env["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count="
                f"{devices_per_process}")
        if use_cpu:
            # keep only the package root on the path
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = pkg_root
        else:
            # accelerator workers still need the package importable
            # when it is not pip-installed
            env["PYTHONPATH"] = os.pathsep.join(
                [pkg_root] + ([env["PYTHONPATH"]]
                              if env.get("PYTHONPATH") else []))
        # worker output goes to FILES: a filled 64KB stderr pipe
        # would stall that rank inside a collective and deadlock
        # the whole fleet until the timeout
        lf = open(os.path.join(td, f"rank{rank}_a{attempt}.log"), "w+b")
        logs.append(lf)
        procs.append(subprocess.Popen(
            [sys.executable, script, cfg_path], env=env,
            stdout=lf, stderr=subprocess.STDOUT))
    return procs, logs


def _kill_cohort(procs) -> None:
    for q in procs:
        if q.poll() is None:
            q.kill()
    for q in procs:
        q.wait()   # reap — no zombies in long-lived hosts


def _tail(logs, rank: int) -> str:
    try:
        logs[rank].seek(0)
        return logs[rank].read().decode(errors="replace")[-1500:]
    except Exception:
        return "<log unavailable>"


def train_distributed(params: Dict, data_path: str, num_processes: int,
                      num_boost_round: int = 100,
                      dataset_params: Optional[Dict] = None,
                      devices_per_process: int = 0,
                      coordinator_address: Optional[str] = None,
                      use_cpu: bool = True, timeout: float = 3600.0,
                      max_restarts: Optional[int] = None,
                      restart_backoff: Optional[float] = None,
                      fault_env: Optional[Dict[str, str]] = None):
    """Train ONE model with ``num_processes`` local worker processes over
    per-rank shards of ``data_path``; returns rank 0's Booster (every
    rank holds the identical model — tests/test_multiproc_train.py).

    ``devices_per_process`` > 0 forces that many virtual CPU devices per
    worker (XLA_FLAGS); ``use_cpu=False`` leaves the platform to the
    runtime and therefore allows ONE local worker only: every local
    worker would see the whole host's chips, a chip belongs to one
    process, and the second worker could not have one. Several chips
    on one host are one process (``tree_learner=data`` over
    ``jax.devices()``), not several workers. The reference flow being
    mirrored: dask.py _train — partition per worker, port negotiation,
    per-worker local fit, rank-0 booster returned, others discarded.

    Fault tolerance: when ``params`` carry ``checkpoint_period`` (with
    ``checkpoint_dir`` defaulting to launcher scratch), a dead rank
    triggers cohort kill → newest all-rank-consistent checkpoint
    selection → respawn resuming from it, up to ``max_restarts`` times
    (default: the ``restart_max_retries`` param key, 2) with
    ``restart_backoff * 2^attempt`` seconds between attempts.
    ``fault_env`` injects chaos-test env vars (LIGHTGBM_TPU_FAULTS=...)
    into the workers; fired-fault markers persist across respawns so an
    injected crash fires exactly once.
    """
    from ..basic import Booster

    if not use_cpu and num_processes > 1:
        log.fatal(
            "train_distributed(use_cpu=False, num_processes=%d): the "
            "launcher starts its workers on this host, every one would "
            "claim the same chips, and a chip belongs to one process. "
            "One process drives all local chips: call lgb.train with "
            "tree_learner=data (it shards over jax.devices()), or start "
            "one worker per host with coordinator_address",
            num_processes)
    params = dict(params)
    params.setdefault("tree_learner", "data")
    if max_restarts is None:
        max_restarts = int(params.get("restart_max_retries", 2))
    if restart_backoff is None:
        restart_backoff = float(params.get("restart_backoff", 1.0))
    ckpt_period = int(params.get("checkpoint_period", 0) or 0)
    with tempfile.TemporaryDirectory(prefix="lgbm_tpu_launch_") as td:
        ckpt_dir = str(params.get("checkpoint_dir", "") or "")
        if ckpt_period > 0 and not ckpt_dir:
            ckpt_dir = os.path.join(td, "checkpoints")
            params["checkpoint_dir"] = ckpt_dir
        extra_env = dict(fault_env or {})
        # fired-fault markers shared across respawns: an injected crash
        # fires once per launcher call, not once per cohort attempt
        extra_env.setdefault(FAULT_STATE_ENV,
                             os.path.join(td, "fault_state"))
        script = os.path.join(td, "worker.py")
        with open(script, "w") as fh:
            fh.write(_WORKER)
        out = os.path.join(td, "model.txt")
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        deadline = time.time() + timeout
        attempt = 0
        resume = ""
        metrics_port = int(params.get("metrics_port", 0) or 0)
        if metrics_port > 0:
            # each rank's _setup_telemetry binds metrics_port + rank —
            # say where the endpoints are so the operator does not have
            # to derive the per-rank offsets from the docs
            log.info(
                "live OpenMetrics endpoints: %s (rank 0 also serves the "
                "fleet counter view)",
                ", ".join(f"http://127.0.0.1:{metrics_port + r}/metrics"
                          for r in range(num_processes)))
        while True:
            coord = coordinator_address or f"127.0.0.1:{_free_port()}"
            procs, logs = _spawn_cohort(
                td, script, params, data_path, num_processes,
                num_boost_round, dataset_params, out, coord,
                devices_per_process, use_cpu, pkg_root, resume, attempt,
                extra_env)
            failed_rank = None
            rc = None
            try:
                # poll, don't wait sequentially: the cohort must die
                # TOGETHER the moment one rank does — the survivors are
                # wedged inside a collective with a dead peer
                while True:
                    states = [q.poll() for q in procs]
                    bad = [(r, s) for r, s in enumerate(states)
                           if s is not None and s != 0]
                    if bad:
                        failed_rank, rc = bad[0]
                        break
                    if all(s == 0 for s in states):
                        break
                    if time.time() > deadline:
                        _kill_cohort(procs)
                        log.fatal("distributed training timed out after "
                                  "%.0fs (attempt %d)", timeout, attempt)
                    time.sleep(0.2)
            finally:
                if failed_rank is not None:
                    _kill_cohort(procs)
            if failed_rank is None:
                for lf in logs:
                    lf.close()
                break   # clean finish
            tail = _tail(logs, failed_rank)
            for lf in logs:
                lf.close()
            attempt += 1
            if attempt > max_restarts:
                log.fatal(
                    "distributed training failed after %d restart(s): "
                    "rank %d rc=%s: %s", max_restarts, failed_rank, rc,
                    tail)
            resume = (select_checkpoint(ckpt_dir, num_processes) or "") \
                if ckpt_dir else ""
            backoff = restart_backoff * (2 ** (attempt - 1))
            log.warning(
                "rank %d died (rc=%s); killed the cohort, restarting in "
                "%.1fs (attempt %d/%d) from %s\n%s", failed_rank, rc,
                backoff, attempt, max_restarts,
                resume or "scratch (no complete checkpoint)", tail[-400:])
            time.sleep(backoff)
        with open(out) as fh:
            return Booster(model_str=fh.read())
