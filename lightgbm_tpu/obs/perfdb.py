"""Persistent, shape-keyed performance database of measured samples.

The roofline plane (obs/kernelstats.py) turns a profile window into
measured per-executable device times — but a single window is one
sample on one run.  A tuning rule (and every on-chip A/B in ROADMAP
queue A) needs those samples to ACCUMULATE across
runs into a durable, queryable history instead of one-off JSON blobs.
That history is this file format:

- **append-only JSONL** at ``perf_db=<path>`` — each line one sample,
  serialized into a single ``os.write`` to an ``O_APPEND`` descriptor,
  so concurrent writers (two bench runs, a training job and an
  ablation sweep) interleave whole lines, never torn ones;
- **schema-versioned** — every row carries ``schema``; ``load()``
  skips rows from a different major (and malformed lines) with a
  count, so a format bump never crashes an old reader;
- **shape-keyed** — rows are keyed by ``key_id``, a digest of
  (signature, kind, shape class, backend, quant bits, packed layout,
  world size): the tuple that determines which measured samples are
  comparable.  Same model shape + same backend + same layout knobs →
  same key → the samples form a distribution the autotuner (and
  ``scripts/run_diff.py --perf-db``) can consult at trace time.

Writers: the profile-window close hook in boosting/gbdt.py,
``bench.py`` and ``scripts/ablate_hist.py``.  Readers:
``scripts/perfdb_query.py`` and ``scripts/run_diff.py``.
docs/Observability.md §15 documents the row schema.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional

SCHEMA = "lightgbm_tpu.perfdb/1"

#: the comparability tuple — two samples share a key iff all of these
#: match (docs/Observability.md §15)
KEY_FIELDS = ("signature", "kind", "shape_class", "backend",
              "quant_bits", "packed_layout", "world_size")


def make_key(signature: str, kind: str, shape_class: str, backend: str,
             quant_bits: int = 0, packed_layout: bool = False,
             world_size: int = 1) -> Dict[str, Any]:
    """Canonical key dict (KEY_FIELDS order) with its ``key_id``
    digest attached."""
    key = {
        "signature": str(signature), "kind": str(kind),
        "shape_class": str(shape_class), "backend": str(backend),
        "quant_bits": int(quant_bits),
        "packed_layout": bool(packed_layout),
        "world_size": int(world_size),
    }
    canon = json.dumps([key[f] for f in KEY_FIELDS],
                       separators=(",", ":"))
    key["key_id"] = hashlib.sha1(canon.encode()).hexdigest()[:16]
    return key


def sample(key: Dict[str, Any], *, dispatches: int,
           device_time_us_per_dispatch: float,
           measured_fraction: Optional[float] = None,
           achieved_flops_per_s: Optional[float] = None,
           achieved_bytes_per_s: Optional[float] = None,
           source: str = "", run_id: str = "",
           extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One measured row.  ``key`` comes from ``make_key``; measurement
    fields come from a joined roofline executable."""
    row: Dict[str, Any] = {
        "schema": SCHEMA,
        "key_id": key.get("key_id", ""),
        "key": {f: key.get(f) for f in KEY_FIELDS},
        "dispatches": int(dispatches),
        "device_time_us_per_dispatch": round(
            float(device_time_us_per_dispatch), 3),
        "source": str(source), "run_id": str(run_id),
        "ts": round(time.time(), 3),
    }
    if measured_fraction is not None:
        row["measured_fraction"] = round(float(measured_fraction), 6)
    if achieved_flops_per_s is not None:
        row["achieved_flops_per_s"] = float(achieved_flops_per_s)
    if achieved_bytes_per_s is not None:
        row["achieved_bytes_per_s"] = float(achieved_bytes_per_s)
    if extra:
        row.update(extra)
    return row


def samples_from_roofline(roofline: Dict[str, Any], *, shape_class: str,
                          backend: str, quant_bits: int = 0,
                          packed_layout: bool = False,
                          world_size: int = 1, source: str = "",
                          run_id: str = "") -> List[Dict[str, Any]]:
    """Every JOINED executable of a roofline record (kernelstats
    ``join_cost`` output) with non-zero measured device time -> one
    perfdb row.  Unjoined anchors have no signature to key on and are
    skipped (they already show up as join_coverage < 1.0)."""
    rows: List[Dict[str, Any]] = []
    for ex in roofline.get("executables", []) or []:
        if not ex.get("joined") or not ex.get("signature"):
            continue
        per_disp = ex.get("device_time_us_per_dispatch")
        if not isinstance(per_disp, (int, float)) or per_disp <= 0:
            continue
        key = make_key(ex["signature"], ex.get("kind", "?"),
                       shape_class, backend, quant_bits=quant_bits,
                       packed_layout=packed_layout,
                       world_size=world_size)
        extra = {}
        if ex.get("timing_source"):
            extra["timing_source"] = str(ex["timing_source"])
        rows.append(sample(
            key, dispatches=int(ex.get("dispatches", 0)),
            device_time_us_per_dispatch=float(per_disp),
            measured_fraction=ex.get("measured_fraction"),
            achieved_flops_per_s=ex.get("achieved_flops_per_s"),
            achieved_bytes_per_s=ex.get("achieved_bytes_per_s"),
            source=source, run_id=run_id, extra=extra))
    return rows


class PerfDB:
    """One perf database file.  Stateless beyond the path — every
    ``append`` opens, writes once and closes, so the handle never
    outlives a training run or pins a deleted file."""

    def __init__(self, path: str):
        self.path = str(path)

    # ---------------------------------------------------------- write
    def append(self, rows: List[Dict[str, Any]]) -> int:
        """Atomically append rows (one buffered ``os.write`` to an
        ``O_APPEND`` fd — concurrent appenders interleave whole lines).
        Returns the number of rows written; never raises (a perf
        database must never be the reason training dies)."""
        rows = [r for r in rows or [] if isinstance(r, dict)]
        if not rows:
            return 0
        try:
            buf = "".join(
                json.dumps(r, sort_keys=True, default=str) + "\n"
                for r in rows).encode("utf-8")
            parent = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(parent, exist_ok=True)
            fd = os.open(self.path,
                         os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            try:
                os.write(fd, buf)
            finally:
                os.close(fd)
            return len(rows)
        except (OSError, TypeError, ValueError):
            return 0

    # ----------------------------------------------------------- read
    def load(self) -> Dict[str, Any]:
        """Read every well-formed same-major row.  Malformed lines and
        foreign-schema rows are counted in ``skipped``, never raised —
        an interrupted writer or a future format must not brick the
        reader."""
        rows: List[Dict[str, Any]] = []
        skipped = 0
        major = SCHEMA.rsplit("/", 1)[0]
        try:
            with open(self.path) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        skipped += 1
                        continue
                    if not isinstance(row, dict) or not str(
                            row.get("schema", "")).startswith(
                                major + "/"):
                        skipped += 1
                        continue
                    rows.append(row)
        except OSError:
            pass
        return {"rows": rows, "skipped": skipped}

    def query(self, rows: Optional[List[Dict[str, Any]]] = None,
              **filters: Any) -> List[Dict[str, Any]]:
        """Filter rows by key fields (``signature`` matches on the
        full string OR its pre-``[`` base) and/or ``key_id`` /
        ``source``."""
        if rows is None:
            rows = self.load()["rows"]
        out = []
        for row in rows:
            key = row.get("key", {}) or {}
            ok = True
            for f, want in filters.items():
                if want in (None, ""):
                    continue
                if f in ("key_id", "source", "run_id"):
                    have = row.get(f)
                elif f == "signature":
                    have = key.get(f)
                    if have != want and str(have or "").split(
                            "[", 1)[0] != want:
                        ok = False
                        break
                    continue
                else:
                    have = key.get(f)
                if str(have) != str(want):
                    ok = False
                    break
            if ok:
                out.append(row)
        return out


def summarize(rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Group rows by key_id -> per-key summaries (sample count,
    mean/min/max/last measured device time per dispatch, best achieved
    rates), sorted by sample count then mean time — the
    ``perfdb_query.py`` view and run_diff's baseline source."""
    by_key: Dict[str, List[Dict[str, Any]]] = {}
    for row in rows:
        by_key.setdefault(str(row.get("key_id", "?")), []).append(row)
    out: List[Dict[str, Any]] = []
    for key_id, group in by_key.items():
        times = [float(r["device_time_us_per_dispatch"]) for r in group
                 if isinstance(r.get("device_time_us_per_dispatch"),
                               (int, float))]
        ent: Dict[str, Any] = {
            "key_id": key_id,
            "key": dict(group[-1].get("key", {}) or {}),
            "samples": len(group),
            "sources": sorted({str(r.get("source", "?"))
                               for r in group}),
        }
        if times:
            ent["device_time_us_per_dispatch"] = {
                "mean": round(sum(times) / len(times), 3),
                "min": round(min(times), 3),
                "max": round(max(times), 3),
                "last": round(times[-1], 3),
            }
        flops = [float(r["achieved_flops_per_s"]) for r in group
                 if isinstance(r.get("achieved_flops_per_s"),
                               (int, float))]
        if flops:
            ent["achieved_flops_per_s_best"] = max(flops)
        byts = [float(r["achieved_bytes_per_s"]) for r in group
                if isinstance(r.get("achieved_bytes_per_s"),
                              (int, float))]
        if byts:
            ent["achieved_bytes_per_s_best"] = max(byts)
        out.append(ent)
    out.sort(key=lambda e: (-e["samples"], e.get(
        "device_time_us_per_dispatch", {}).get("mean", 0.0)))
    return out
