"""Device-time cost ledger: analytic FLOP/byte accounting per executable.

Fourteen PRs of instrumentation measure *when* the device is busy
(dispatch counters, batch wall times, compile events) but not *what the
work is worth*: answering "how many FLOPs and HBM bytes does one
boosting iteration actually move, and what fraction does the analytic
histogram model (``hist.*`` gauges, ops/layout.hist_plane_bytes)
account for?" still required hand-joining JSONL sinks.  The ledger
closes that gap in the spirit of the accelerator cost models of
arxiv 2011.02022 and the whole-loop-on-device accounting of
arxiv 1706.08359:

- **per-executable analysis** — every fresh jit signature the drivers
  detect (megastep chunks, the per-iteration fast step, serving
  buckets) is queued here with its *abstract* operand shapes
  (``jax.ShapeDtypeStruct`` — never live buffers, so donation cannot
  invalidate the queue) and analyzed lazily OFF the dispatch path via
  ``fn.lower(...)``: ``cost_ledger="hlo"`` (default) reads
  ``Lowered.cost_analysis()`` (client-side HLO analysis, no second XLA
  compile), ``"compiled"`` reads ``lowered.compile().cost_analysis()``
  (the post-optimization executable numbers the ISSUE names — pays a
  second backend compile unless the persistent compilation cache is
  armed via ``compilation_cache_dir``);
- **per-iteration attribution** — one ``cost_ledger`` JSONL record per
  drained batch joins the executable analysis (scaled by the chunk
  length it covers) with the batch's measured wall time, the measured
  in-trace collective payload (ops/collectives.py) and the analytic
  ``hist.bytes_per_iter`` plane model, and gauges
  ``cost.flops_per_iter`` / ``cost.hlo_bytes_per_iter`` /
  ``cost.achieved_fraction`` for the exporter;
- **ground truth for the analytic model** — ``achieved_fraction`` is
  ``hist.bytes_per_iter / cost.hlo_bytes_per_iter``: the share of the
  executable's total HLO byte traffic the PR-14 analytic histogram
  model accounts for.  A layout change that moves the fraction without
  touching either model is a real attribution shift, not noise.

Honesty caveat (documented in docs/Observability.md §12): HLO cost
analysis prices custom calls (the Pallas histogram kernel) at their
operand traffic, not their internal loops — the ``hist.*`` analytic
model is the complementary in-kernel view, which is exactly why the
ledger reports both sides instead of pretending one is ground truth.

Every entry point is exception-safe and a no-op on a disabled registry:
a cost model must never be the reason a training run dies.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from ..utils import log

MODES = ("off", "hlo", "compiled")


def tree_avals(tree):
    """Pytree of arrays -> pytree of ShapeDtypeStructs (non-array leaves
    pass through).  Shape/dtype metadata stays readable even on donated
    (deleted) device buffers, so this is safe to call after dispatch."""
    import jax

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(conv, tree)


def _merge_analysis(ca: Any) -> Dict[str, float]:
    """Normalize cost_analysis output: newer jax returns one dict,
    older backends a list of per-computation dicts — sum the families
    we report."""
    if isinstance(ca, dict):
        parts: List[Dict[str, Any]] = [ca]
    elif isinstance(ca, (list, tuple)):
        parts = [p for p in ca if isinstance(p, dict)]
    else:
        parts = []
    out = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    for p in parts:
        out["flops"] += float(p.get("flops", 0.0) or 0.0)
        out["bytes_accessed"] += float(p.get("bytes accessed", 0.0) or 0.0)
        out["transcendentals"] += float(p.get("transcendentals", 0.0)
                                        or 0.0)
    return out


def analyze_jit(fn, args, kwargs=None, mode: str = "hlo"
                ) -> Optional[Dict[str, float]]:
    """Cost-analyze one jitted callable against abstract args.  Returns
    ``{"flops", "bytes_accessed", "transcendentals"}`` or None when the
    backend/API cannot answer (never raises)."""
    if mode == "off":
        return None
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
        if mode == "compiled":
            ca = lowered.compile().cost_analysis()
        else:
            ca = lowered.cost_analysis()
        return _merge_analysis(ca)
    except Exception as e:     # the ledger is advisory, training is not
        log.debug("cost analysis failed: %s", e)
        return None


class CostLedger:
    """Per-run executable cost bookkeeping over one Telemetry registry.

    ``note()`` is cheap (aval capture + queue append) and safe on the
    dispatch path; ``flush()`` runs the deferred analyses and is meant
    for host-sync points (megastep drain, serve warmup/post-batch);
    ``ledger_record()`` emits the per-drained-batch join.
    """

    #: executable kinds that drive the per-iteration training gauges
    TRAIN_KINDS = ("megastep", "fast_step")

    def __init__(self, tel, mode: str = "hlo"):
        self.tel = tel
        self.mode = mode if mode in MODES else "hlo"
        self._lock = threading.Lock()
        self._pending: List[Dict[str, Any]] = []
        # newest analyzed entry per kind (the megastep re-chunks near
        # horizon tails; the latest signature is the active schedule)
        self._by_kind: Dict[str, Dict[str, Any]] = {}
        self._analyzed: Dict[str, Dict[str, Any]] = {}

    @property
    def enabled(self) -> bool:
        return self.mode != "off" and self.tel is not None \
            and self.tel.enabled

    # ------------------------------------------------------------------
    def note(self, fn, args, signature: str, kind: str, scale: int = 1,
             kwargs=None, operand_bytes: int = 0, **extra: Any) -> None:
        """Queue a fresh executable signature for deferred analysis.
        ``scale`` is how many iterations (training) or rows (serving)
        one call of the executable covers."""
        if not self.enabled:
            return
        try:
            avals = tree_avals(args)
            kw_avals = tree_avals(kwargs) if kwargs else None
        except Exception as e:
            log.debug("cost aval capture failed: %s", e)
            return
        with self._lock:
            if signature in self._analyzed:
                return
            self._pending.append({
                "fn": fn, "args": avals, "kwargs": kw_avals,
                "signature": str(signature), "kind": str(kind),
                "scale": max(1, int(scale)),
                "operand_bytes": int(operand_bytes), "extra": extra})

    def flush(self) -> None:
        """Run deferred analyses (host-sync points only: fn.lower costs
        a retrace).  Emits one ``cost_executable`` event per signature —
        the record that joins against ``compile_executable`` by
        signature string."""
        if not self.enabled:
            return
        with self._lock:
            pending, self._pending = self._pending, []
        for ent in pending:
            # (a retrace and a lowering of the whole step: seconds for a
            # megastep, so it is a span of its own on the stream)
            with self.tel.timed("cost/analyze", signature=ent["signature"],
                                mode=self.mode):
                ca = analyze_jit(ent["fn"], ent["args"], ent["kwargs"],
                                 self.mode)
            if ca is None:
                self.tel.inc("cost.analysis_failed")
                continue
            rec = {"signature": ent["signature"], "kind": ent["kind"],
                   "scale": ent["scale"],
                   "operand_bytes": ent["operand_bytes"],
                   "flops": ca["flops"],
                   "hlo_bytes": ca["bytes_accessed"],
                   "transcendentals": ca["transcendentals"],
                   "mode": self.mode}
            with self._lock:
                self._analyzed[ent["signature"]] = rec
                self._by_kind[ent["kind"]] = rec
            self.tel.inc("cost.executables")
            self.tel.event("cost_executable", **dict(rec, **ent["extra"]))

    # ------------------------------------------------------------------
    def active_train_entry(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            for kind in self.TRAIN_KINDS:
                if kind in self._by_kind:
                    return dict(self._by_kind[kind])
        return None

    def entry(self, kind: str) -> Optional[Dict[str, Any]]:
        """Newest analyzed entry of one kind (None before any flush)."""
        with self._lock:
            ent = self._by_kind.get(kind)
            return dict(ent) if ent else None

    @property
    def has_pending(self) -> bool:
        with self._lock:
            return bool(self._pending)

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(v) for v in self._analyzed.values()]

    def ledger_record(self, it0: int, iterations: int,
                      wall_s: Optional[float] = None,
                      hist_bytes_per_iter: Optional[float] = None,
                      coll_bytes_per_iter: Optional[float] = None
                      ) -> Optional[Dict[str, Any]]:
        """One per-drained-batch join: the active executable's analytic
        FLOPs/bytes scaled per iteration, the measured wall, the
        measured collective payload and the analytic histogram plane
        model — plus the ``cost.*`` gauges the exporter scrapes."""
        if not self.enabled:
            return None
        self.flush()
        ent = self.active_train_entry()
        if ent is None or iterations <= 0:
            return None
        tel = self.tel
        flops_it = ent["flops"] / ent["scale"]
        bytes_it = ent["hlo_bytes"] / ent["scale"]
        tel.gauge("cost.flops_per_iter", flops_it)
        tel.gauge("cost.hlo_bytes_per_iter", bytes_it)
        rec: Dict[str, Any] = {
            "iterations": int(iterations),
            "kind": ent["kind"], "signature": ent["signature"],
            "mode": ent["mode"],
            "flops_per_iter": flops_it,
            "hlo_bytes_per_iter": bytes_it,
            "operand_bytes": ent["operand_bytes"],
        }
        if wall_s is not None and wall_s > 0:
            sec_it = wall_s / iterations
            rec["sec_per_iter"] = round(sec_it, 6)
            rec["achieved_flops_per_s"] = flops_it / sec_it
            rec["achieved_bytes_per_s"] = bytes_it / sec_it
        if coll_bytes_per_iter is not None:
            rec["coll_bytes_per_iter"] = float(coll_bytes_per_iter)
        if hist_bytes_per_iter is not None and hist_bytes_per_iter > 0 \
                and bytes_it > 0:
            frac = float(hist_bytes_per_iter) / bytes_it
            rec["hist_bytes_per_iter"] = float(hist_bytes_per_iter)
            rec["achieved_fraction"] = frac
            tel.gauge("cost.achieved_fraction", frac)
        tel.event("cost_ledger", iteration=int(it0), **rec)
        return rec
