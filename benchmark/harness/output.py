"""The one line the driver reads: the last line of standard output."""
from __future__ import annotations

import json

from . import monitor


def _device(run, window) -> dict:
    d0 = run.devices[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(run.devices),
           "memory_peak_bytes": max(monitor.memory_peaks(run.devices))}
    if window is not None:
        used = window.reduced.devices
        out["busy_s"] = sum(window.busy_ns(d) for d in used) / len(used) / 1e9
        out["window_s"] = window.seconds
    return out


def breakdown(window, top: int = 10) -> dict:
    """The operations that took most device time (mean over the chips)
    and the longest idle gaps of the idlest chip, each labelled by what
    the host was doing."""
    used = window.reduced.devices
    ops = {}
    for dev in used:
        for name, sec in window.totals(dev):
            ops[name] = ops.get(name, 0.0) + sec / len(used)
    idlest = min(used, key=window.busy_ns)
    gaps = sorted(window.gaps(idlest), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[window.host_label(a, b), (b - a) / 1e9]
                      for a, b in gaps],
    }


def line(run, manifest_metrics: list, values: dict, result: dict) -> str:
    """``values`` maps metric name to a number; only metrics the manifest
    lists for this cell are printed, each with the manifest's unit. A
    rehearsal leaves ``metrics`` empty and says under ``rehearsal`` which
    it produced, with the value only of exact counts: a time from the CPU
    never stands under a device metric's name."""
    units = {m["name"]: m["unit"] for m in manifest_metrics}
    metrics = {name: {"value": values[name], "unit": units[name]}
               for name in units if values.get(name) is not None}
    window = run.window         # set only by a traced run on the chip
    out = {"correct": not result["problems"],
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"]),
           "metrics": {} if run.rehearsal else metrics,
           "device": _device(run, window)}
    if run.rehearsal:
        counted = {m["name"] for m in manifest_metrics
                   if m["source"] == "program_counter"}
        out["rehearsal"] = {
            "produced": sorted(metrics),
            "counts": {n: v["value"] for n, v in metrics.items()
                       if n in counted}}
    if window is not None:
        out["breakdown"] = breakdown(window)
    out["workload"] = run.cell["name"]
    out["seed"] = run.seed
    out["problems"] = result["problems"]
    out["checks"] = {k: run.facts[k] for k in ("own_auc", "traced_auc")
                     if k in run.facts}
    out["phases_s"] = run.phases
    return json.dumps(out)
