"""Thread-safe telemetry registry.

One :class:`Telemetry` instance per booster (GBDT driver).  It holds

- **counters** — monotone sums (iterations, collective bytes, degrade
  reasons, compile events);
- **gauges** — last-written values (device memory, bag counts);
- **timings** — per-name duration distributions ``{count, total, min,
  max}`` fed by the driver's per-iteration sections and by compile
  events;
- **events** — a bounded ring of structured records, mirrored to the
  JSONL sink when one is attached (``telemetry_out=<path>``);
- **records** — completed per-iteration records queued for the
  ``record_telemetry`` callback to drain;
- **spans** — wall-clock (start, duration) pairs collected only when the
  trace exporter is on (``trace_out=<path>``), drained by obs.trace into
  a Perfetto/Chrome-trace timeline (one track per rank);
- **set-up spans** — :class:`Span` (``Telemetry.timed``): the ONE timed
  span of everything outside the boosting loop (binning, upload and
  pack, a step's first call, the end of ``engine.train``), written as one
  ``setup_span`` event per closed span with its start on the clock of
  every event's ``ts`` (docs/Observability.md §3b).

Disabled-path contract: every recording method returns after a single
``self.enabled`` attribute check — no allocation, no locking, no
serialization — so the instrumentation can live in the training loop
permanently (the acceptance bar the ISSUE sets for the disabled path).

Rank handling: every record is tagged with ``jax.process_index()``;
``allgather_json`` is the SPMD helper the driver uses to aggregate
per-rank counter snapshots at rank 0 when emitting the end-of-training
summary.
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..utils.timer import global_timer as timer

_EVENT_RING = 512       # bounded in-memory event history
_RECORD_RING = 65536    # per-iteration records awaiting a drain
_SPAN_RING = 16384      # trace spans awaiting export (a few per iteration)
_FINDING_RING = 1024    # health/guard findings kept for the whole run
_DIST_RING = 8192       # recent samples per value distribution
_FINDING_EVENTS = frozenset(
    {"anomaly", "rank_divergence", "straggler", "alert"})
_HELD_SPANS = 256       # set-up spans closed before the registry is on

# the spans open on this thread, outermost first: what gives a span its
# parent and the jax.monitoring listener its ``first_call``
_open = threading.local()


def open_spans() -> List["Span"]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class Span:
    """One timed span of the set-up or the end of a job: the ONE
    primitive (``Telemetry.timed`` makes it). In one place it is a
    ``jax.profiler.TraceAnnotation`` (a ``profile_dir`` trace that covers
    the set-up shows it on the host plane, on the device operations'
    clock), an entry of the crash recorder's section stack, a TIMETAG
    section, a ``trace_out`` span on the ``setup`` track and ONE
    ``setup_span`` JSONL event when it closes: ``name``, ``t0`` (its
    start, ``time.time()``: the clock of every event's ``ts``),
    ``dur_s``, ``parent`` (the span open around it on this thread),
    ``job`` (the registry's ``run_id``) and its attributes.

    ``sync(arrays)`` blocks on them before the span closes, so the
    device work a span started is charged to it and not to whichever
    later call waits; it blocks only while the registry (or the TIMETAG
    timer) is on, so a job with telemetry off syncs nothing more.

    A span needs no live registry: with ``tel=None`` it closes into
    ``hold`` (its own list or the one of the span around it): the
    Dataset's, which is binned before any Booster has a sink, and
    ``engine.train``'s until its Booster exists (``bind``). A registry
    that is not enabled yet keeps the closed span and writes it when it
    is (``record_telemetry`` enables at the first iteration).

    ``adopt=True`` (a step's ``first_call``) takes children known only by
    their bounds, jax's own trace / lower / compile time spans
    (obs/jaxmon.py), and writes them when it closes; an inner jit's trace
    lies inside the step's and is folded into it (``adopt``), so a reader
    that sums the spans whose parent is ``first_call`` counts nothing
    twice."""

    __slots__ = ("tel", "name", "attrs", "hold", "parent", "t0", "_p0",
                 "_sync", "_ann", "_adopted", "cache")

    def __init__(self, tel, name: str, hold: Optional[list] = None,
                 adopt: bool = False, **attrs: Any):
        self.tel = tel
        self.name = name
        self.attrs = attrs
        self.hold = hold
        self.parent: Optional[str] = None
        self._sync = None
        # the outermost children it adopted: (name, t0, t1, attrs)
        self._adopted: Optional[list] = [] if adopt else None
        self.cache: Dict[str, float] = {}   # jaxmon: a hit's durations

    # ------------------------------------------------------------ handle
    def sync(self, arrays) -> None:
        """Block on ``arrays`` before the span closes (while the
        registry or the TIMETAG timer is on)."""
        if self._live():
            self._sync = arrays

    def set(self, **attrs: Any) -> None:
        """Attributes known only inside the span (``bytes``)."""
        self.attrs.update(attrs)

    def bind(self, tel) -> None:
        """Give a span that opened without a registry the one its job
        now has, and hand over what closed into its ``hold`` so far."""
        held, self.hold = self.hold, None
        self.tel = tel
        tel.publish_spans(held or ())

    def adopt(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        """A child known by its bounds, reported when it ENDS (jax's
        phases): the children reported before it that started inside it
        are its own, and fold into it as ``inner_jits`` (how many, at any
        depth) and ``inner``: its direct ones by ``fun_name`` as
        ``[fun_name, count, seconds]``, the eight largest, and where a
        name stands for ONE span that held others, that span's own
        ``inner`` as a fourth item. So a step's thousands of inner jits
        cost its stream three lines and not thousands, and the lines
        still say which jit inside which carries the time."""
        roots = self._adopted
        count, by_fun = 0, {}
        while roots and roots[-1][1] >= t0:
            _, c0, c1, cattrs = roots.pop()
            count += 1 + cattrs.get("inner_jits", 0)
            ent = by_fun.setdefault(str(cattrs.get("fun_name", "?")),
                                    [0, 0.0, None])
            ent[0] += 1
            ent[1] += c1 - c0
            ent[2] = cattrs.get("inner") if ent[0] == 1 else None
        if count:
            top = sorted(by_fun.items(), key=lambda kv: -kv[1][1])[:8]
            attrs.update(inner_jits=count, inner=[
                [fun, n, round(sec, 6)] + ([deeper] if deeper else [])
                for fun, (n, sec, deeper) in top])
        roots.append((name, float(t0), float(t1), attrs))

    @property
    def adopts(self) -> bool:
        return self._adopted is not None

    def _live(self) -> bool:
        tel = self.tel
        return (tel is not None and tel.enabled) or timer.enabled

    # ----------------------------------------------------------- context
    def __enter__(self) -> "Span":
        stack = open_spans()
        if stack:
            above = stack[-1]
            self.parent = above.name
            if self.tel is None and self.hold is None:
                self.tel, self.hold = above.tel, above.hold
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        if self.tel is not None:
            self.tel.push_section(self.name)
        self.t0 = time.time()
        self._p0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None and self._sync is not None:
            import jax
            jax.block_until_ready(self._sync)
        self._sync = None
        dur = time.perf_counter() - self._p0
        stack = open_spans()
        if self in stack:           # and whatever an exception left above
            del stack[stack.index(self):]
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            # like GBDT._sec: the section stays on the crash recorder's
            # stack, and a span that did not finish is not written
            return False
        tel = self.tel
        if tel is not None:
            tel.pop_section()
        timer.add(self.name, dur)
        records = [{"name": self.name, "t0": self.t0, "dur_s": dur,
                    "parent": self.parent, **self.attrs}]
        # (what was adopted and not folded into another is this span's)
        records += [{"name": name, "t0": t0, "dur_s": t1 - t0,
                     "parent": self.name, **attrs}
                    for name, t0, t1, attrs in self._adopted or ()]
        for rec in records:
            if tel is not None:
                tel.write_span(rec)
            elif self.hold is not None:
                self.hold.append(rec)
        return False


class Telemetry:
    """Counters + gauges + timing distributions + structured events."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        # stable per-registry run identity: the exporter stamps it on
        # every metric series so scrapes from successive runs on the
        # same port are distinguishable in a time-series store (the
        # entropy tail keeps two registries born in the same second of
        # the same process distinct)
        self.run_id = (f"{int(time.time()):x}-{os.getpid():x}-"
                       f"{os.urandom(2).hex()}")
        self._lock = threading.RLock()
        # latest per-rank counter snapshots (fed by the health auditor's
        # existing allgather — obs/export.py renders rank 0's fleet view
        # from this, adding zero new collectives)
        self._fleet: List[Dict[str, Any]] = []
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._timings: Dict[str, Dict[str, float]] = {}
        self._events = collections.deque(maxlen=_EVENT_RING)
        self._findings = collections.deque(maxlen=_FINDING_RING)
        self._dists: Dict[str, collections.deque] = {}
        # cumulative [count, sum] per dist name: the ring bounds what
        # the QUANTILES cover, but OpenMetrics summary _count/_sum must
        # be monotone or Prometheus rate()/increase() breaks the moment
        # the ring wraps (count pins at maxlen, sum wobbles on evictions)
        self._dist_totals: Dict[str, List[float]] = {}
        self._records = collections.deque(maxlen=_RECORD_RING)
        self._spans = collections.deque(maxlen=_SPAN_RING)
        # set-up spans that closed while the registry was off
        # (write_span); enable() writes them
        self._held_spans: List[Dict[str, Any]] = []
        self._trace_on = False
        # trace timebase: wall-clock epoch + monotonic offsets, so span
        # timestamps stay comparable ACROSS ranks (shared epoch) yet a
        # mid-run NTP step cannot un-nest spans WITHIN a rank the way
        # raw time.time() starts + perf_counter durations would
        self._perf_epoch = time.time() - time.perf_counter()
        self._sink = None
        self._rank: Optional[int] = None
        # live section nesting (crash flight recorder reads this)
        self._section_stack: List[str] = []
        # per-iteration scratch (begin_iteration .. end_iteration)
        self._cur_iter: Optional[int] = None
        self._cur_iter_wall: Optional[float] = None
        self._cur_sections: Dict[str, float] = {}
        self._cur_collectives: Dict[str, Dict[str, int]] = {}
        self._cur_compile: Dict[str, float] = {}

    # ------------------------------------------------------------ admin
    @property
    def rank(self) -> int:
        if self._rank is None:
            try:
                import jax
                self._rank = int(jax.process_index())
            except Exception:
                self._rank = 0
        return self._rank

    def enable(self, sink_path: Optional[str] = None,
               trace: Optional[bool] = None) -> bool:
        """Turn recording on; ``sink_path`` additionally streams every
        event as a JSONL line (rank-suffixed under multi-process) and
        ``trace`` switches wall-clock span collection for the trace
        exporter on/off (``None`` leaves it as is, so an enable() from a
        path that doesn't know about tracing — e.g. record_telemetry —
        can't silently stop an active collection).  Returns True when a
        NEW sink was attached by this call (re-enabling with the path
        already attached is a no-op, so a
        ``reset_parameter(telemetry_out=...)`` round trip neither
        clobbers nor duplicates the stream; a *different* path closes
        the old sink and opens the new one)."""
        from . import jaxmon
        from .events import JsonlSink
        attached = False
        with self._lock:
            if sink_path:
                old = self._sink
                if old is not None and old.requested_path != sink_path:
                    old.close()
                    self._sink = None
                if self._sink is None:
                    self._sink = JsonlSink(sink_path, rank=self.rank)
                    attached = True
            if trace is not None:
                self._trace_on = bool(trace)
            self.enabled = True
            held, self._held_spans = self._held_spans, []
        jaxmon.attach(self)
        self.publish_spans(held)
        return attached

    @property
    def sink_path(self) -> Optional[str]:
        """Path of the attached JSONL sink (rank-suffixed), or None —
        the public view drivers should use instead of ``_sink``."""
        sink = self._sink
        return None if sink is None else sink.path

    def disable(self) -> None:
        from . import jaxmon
        jaxmon.detach(self)
        self.flush()
        self.enabled = False

    def flush(self) -> None:
        sink = self._sink
        if sink is not None:
            sink.flush()

    def close(self) -> None:
        self.disable()
        sink, self._sink = self._sink, None
        if sink is not None:
            sink.close()

    # ------------------------------------------------------- primitives
    def inc(self, name: str, value: float = 1) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._gauges[name] = value

    def gauge_max(self, name: str, value: float) -> None:
        """High-watermark gauge: keeps the maximum ever recorded.  For
        series whose contract is a bound (peak live ingest chunks), a
        plain set() from a later, smaller observation would silently
        erase the violation the gauge exists to expose."""
        if not self.enabled:
            return
        with self._lock:
            prev = self._gauges.get(name)
            if prev is None or value > prev:
                self._gauges[name] = value

    def observe(self, name: str, seconds: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._observe_locked(name, seconds)

    def _observe_locked(self, name: str, seconds: float) -> None:
        t = self._timings.get(name)
        if t is None:
            t = self._timings[name] = {"count": 0, "total": 0.0,
                                       "min": float("inf"), "max": 0.0}
        t["count"] += 1
        t["total"] += seconds
        t["min"] = min(t["min"], seconds)
        t["max"] = max(t["max"], seconds)

    def dist(self, name: str, value: float) -> None:
        """Value-distribution sample (request latencies, micro-batch
        sizes): kept in a bounded ring per name so the snapshot can
        report real p50/p95/p99 quantiles, which the {count,total,
        min,max} ``observe`` timings cannot.  The ring bounds memory;
        quantiles cover the most recent ``_DIST_RING`` samples."""
        if not self.enabled:
            return
        with self._lock:
            d = self._dists.get(name)
            if d is None:
                d = self._dists[name] = collections.deque(
                    maxlen=_DIST_RING)
                self._dist_totals[name] = [0, 0.0]
            d.append(float(value))
            tot = self._dist_totals[name]
            tot[0] += 1
            tot[1] += float(value)

    @staticmethod
    def _dist_summary(samples, totals=None) -> Dict[str, float]:
        vals = sorted(samples)
        n = len(vals)
        count, total = (totals if totals is not None
                        else (n, float(sum(vals))))
        if n == 0:
            # empty-ring-safe: a dist observed zero samples (or whose
            # ring was drained) must summarize to count/sum only —
            # NEVER NaN quantiles; the exporter renders quantile series
            # only when count > 0
            return {"count": int(count), "sum": float(total)}

        def q(p: float) -> float:
            return vals[min(n - 1, int(p * (n - 1) + 0.5))]

        return {"count": int(count), "sum": float(total),
                "min": vals[0], "max": vals[-1],
                "p50": q(0.50), "p95": q(0.95), "p99": q(0.99)}

    def event(self, name: str, iteration: Optional[int] = None,
              **attrs: Any) -> None:
        """Structured event: ring-buffered, counted, sunk to JSONL."""
        if not self.enabled:
            return
        rec: Dict[str, Any] = {"ts": time.time(), "rank": self.rank,
                               "event": name}
        if iteration is not None:
            rec["iter"] = int(iteration)
        rec.update(attrs)
        self._record(rec)

    def _record(self, rec: Dict[str, Any]) -> None:
        name = rec["event"]
        with self._lock:
            self._events.append(rec)
            if name in _FINDING_EVENTS:
                # health/guard findings survive in their own ring: the
                # general event ring evicts them within ~500 iterations,
                # but "did anything go wrong" must answer for the whole
                # run (record_telemetry's anomalies list reads this)
                self._findings.append(rec)
            key = "events." + name
            self._counters[key] = self._counters.get(key, 0) + 1
            sink = self._sink
        if sink is not None:
            sink.write(rec)

    def anomaly(self, kind: str, iteration: Optional[int] = None,
                **attrs: Any) -> None:
        """Numerical-guard finding (non-finite gradients, histogram or
        tree outputs, degenerate gain distributions): counted under
        ``anomalies.<kind>`` and emitted as a structured ``anomaly``
        event — the record IS the alarm, not a log string."""
        if not self.enabled:
            return
        self.inc("anomalies." + kind)
        self.event("anomaly", iteration=iteration, kind=kind, **attrs)

    def degrade(self, reason: str, **attrs: Any) -> None:
        """A requested mode/engine fell back: the reason is the record,
        not a log string (the registry's analog of the driver's
        log.warning degradation messages)."""
        if not self.enabled:
            return
        self.inc("degrade." + reason)
        self.event("degrade", reason=reason, **attrs)

    # ------------------------------------------------------ trace spans
    def wall_now(self) -> float:
        """Monotonic 'wall clock' for span starts: the process-start
        wall epoch plus a perf_counter offset.  Every span producer must
        use this (not time.time()) so durations and starts share one
        clock and nesting survives NTP steps."""
        return self._perf_epoch + time.perf_counter()

    def span(self, name: str, wall_start: float, seconds: float,
             track: str = "train", iteration: Optional[int] = None,
             **attrs: Any) -> None:
        """Wall-clock span for the trace exporter (collected only while
        ``trace_out`` turned span collection on; ``seconds == 0`` renders
        as an instant event)."""
        if not (self.enabled and self._trace_on):
            return
        rec: Dict[str, Any] = {"name": name, "ts": float(wall_start),
                               "dur": float(seconds), "rank": self.rank,
                               "track": track}
        if iteration is not None:
            rec["iter"] = int(iteration)
        if attrs:
            rec["args"] = attrs
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                # ring is full: the append below evicts the oldest span,
                # truncating the front of the exported timeline — count
                # it so trace_written can say so instead of lying
                self._counters["trace.spans_dropped"] = \
                    self._counters.get("trace.spans_dropped", 0) + 1
            self._spans.append(rec)

    # ----------------------------------------------------- set-up spans
    def timed(self, name: str, adopt: bool = False, **attrs: Any) -> Span:
        """``with tel.timed("init/upload", bytes=n) as s: ...;
        s.sync(x)``: the one span of the set-up and of the end of a job
        (:class:`Span`)."""
        return Span(self, name, adopt=adopt, **attrs)

    def write_span(self, span: Dict[str, Any]) -> None:
        """One closed span (``name``, ``t0``, ``dur_s``, ``parent`` and
        its attributes) as a ``setup_span`` event of this job, and under
        ``trace_out`` as a span of the ``setup`` track; kept for
        ``enable()`` while the registry is off."""
        if not self.enabled:
            if len(self._held_spans) < _HELD_SPANS:
                self._held_spans.append(span)
            return
        span = dict(span)
        rec: Dict[str, Any] = {
            "ts": time.time(), "rank": self.rank, "event": "setup_span",
            "name": span.pop("name"), "t0": span.pop("t0"),
            "dur_s": round(span.pop("dur_s"), 6),
            "parent": span.pop("parent", None), "job": self.run_id}
        rec.update(span)
        self._record(rec)
        self.span(rec["name"], rec["t0"], rec["dur_s"], track="setup",
                  **span)

    def publish_spans(self, spans) -> None:
        """Spans that closed before this registry could take them (a
        Dataset's ``setup_spans``, ``engine.train``'s before its Booster
        existed), with their true ``t0``."""
        for span in spans:
            self.write_span(span)

    def drain_spans(self) -> List[Dict[str, Any]]:
        """Collected trace spans since the last drain (the trace
        exporter's feed; cleared so a second finalize writes nothing)."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    # ------------------------------------------------- crash bookkeeping
    def push_section(self, name: str) -> None:
        """Driver section entry — the stack is what the crash flight
        recorder dumps as 'where training was' when an exception
        unwinds."""
        if self.enabled:
            self._section_stack.append(name)

    def pop_section(self) -> None:
        if self.enabled and self._section_stack:
            self._section_stack.pop()

    def crash_payload(self) -> Dict[str, Any]:
        """Flight-recorder view: the full event ring (not the JSONL
        tail, which may be lost in a crash), the live section stack and
        the counter/gauge state — everything the registry knows at the
        moment of an exception."""
        with self._lock:
            return {
                "rank": self.rank,
                "section_stack": list(self._section_stack),
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "events": [dict(e) for e in self._events],
                "findings": [dict(e) for e in self._findings],
            }

    # ---------------------------------------------------- per-iteration
    def begin_iteration(self, it: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            # a caught-and-recovered exception leaves its sections on the
            # stack (pop is clean-exit only); a fresh iteration starting
            # means the unwind is over, so the stale entries would only
            # mislead a later crash dump
            self._section_stack.clear()
            self._cur_iter = int(it)
            self._cur_iter_wall = self.wall_now()
            self._cur_sections = {}
            self._cur_collectives = {}
            self._cur_compile = {"count": 0, "secs": 0.0}

    def section(self, name: str, seconds: float,
                wall_start: Optional[float] = None) -> None:
        """Accumulate a named section's duration into the current
        iteration record and the global timing distribution (plus a
        trace span when the caller knows the wall-clock start)."""
        if not self.enabled:
            return
        with self._lock:
            self._cur_sections[name] = (self._cur_sections.get(name, 0.0)
                                        + seconds)
            self._observe_locked("section." + name, seconds)
            it = self._cur_iter
        if wall_start is not None:
            self.span(name, wall_start, seconds, track="train",
                      iteration=it)

    def collective(self, kind: str, count: int, nbytes: int,
                   seconds: Optional[float] = None,
                   wall_start: Optional[float] = None) -> None:
        """Record collective traffic (count + payload bytes) against the
        current iteration (if one is open) and the global counters.
        Real (host-plane) collectives pass their measured ``seconds`` —
        they feed the timing distribution and render as trace spans;
        analytic in-jit estimates pass none and render as instants."""
        if not self.enabled:
            return
        with self._lock:
            if self._cur_iter is not None:
                c = self._cur_collectives.setdefault(
                    kind, {"count": 0, "bytes": 0})
                c["count"] += int(count)
                c["bytes"] += int(nbytes)
            self._counters["collectives.count"] = \
                self._counters.get("collectives.count", 0) + int(count)
            self._counters["collectives.bytes"] = \
                self._counters.get("collectives.bytes", 0) + int(nbytes)
            if seconds is not None:
                self._observe_locked("collective." + kind, seconds)
        if self._trace_on:
            self.span(kind,
                      wall_start if wall_start is not None
                      else self.wall_now(),
                      seconds or 0.0, track="collectives",
                      count=int(count), bytes=int(nbytes))

    def compile_event(self, phase: str, seconds: float) -> None:
        """XLA compile phase (fed by obs.jaxmon); attributed to the open
        iteration when one is active: the counters the exporter's
        recompile rate reads, not per-phase JSONL spam (a step's first
        call writes its phases as ``setup_span`` events, obs/jaxmon.py)."""
        if not self.enabled:
            return
        with self._lock:
            self._counters["compile.events"] = \
                self._counters.get("compile.events", 0) + 1
            self._counters["compile.seconds"] = \
                self._counters.get("compile.seconds", 0) + float(seconds)
            self._observe_locked("compile." + phase, seconds)
            if self._cur_iter is not None:
                self._cur_compile["count"] += 1
                self._cur_compile["secs"] += seconds

    def compile_span(self, phase: str, start: float, end: float,
                     **attrs: Any) -> None:
        """The same phase with the bounds jax measured
        (``record_event_time_span``), for the ``compile`` track of
        ``trace_out``."""
        self.span("compile:" + phase, start, end - start, track="compile",
                  **attrs)

    def compile_executable(self, signature: str, compile_ms: float,
                           operand_bytes: int, **attrs: Any) -> None:
        """Per-executable compile accounting: one structured event per
        NEW jit signature (megastep chunk, serving bucket) carrying the
        signature, the first-call wall time (trace + XLA compile) and an
        estimate of the operand bytes the executable touches — the
        record the exporter's recompile-rate and HBM-headroom story
        hangs off (compiles are rare; the event volume is bounded by
        the number of distinct signatures)."""
        if not self.enabled:
            return
        self.inc("compile.executables")
        self.inc("compile.operand_bytes", max(0, int(operand_bytes)))
        self.event("compile_executable", signature=str(signature),
                   compile_ms=round(float(compile_ms), 3),
                   operand_bytes=int(operand_bytes), **attrs)

    # ----------------------------------------------------- fleet counters
    def set_fleet_counters(self, per_rank: List[Dict[str, Any]]) -> None:
        """Store the newest per-rank counter snapshots (each entry
        ``{"rank": r, "counters": {...}}``) — fed by the health
        auditor's existing allgather so the metrics exporter's rank-0
        fleet view costs zero additional collectives."""
        with self._lock:
            self._fleet = [dict(e) for e in per_rank
                           if isinstance(e, dict)]

    def fleet_counters(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._fleet]

    def end_iteration(self, it: int, **attrs: Any) -> Dict[str, Any]:
        """Close the iteration: emit its record (sections, collectives,
        compile activity + caller attrs), queue it for draining and
        return it (the health auditor reads the section times off the
        returned record)."""
        if not self.enabled:
            return {}
        with self._lock:
            sections = {k: round(v, 9)
                        for k, v in self._cur_sections.items()}
            coll = {k: dict(v) for k, v in self._cur_collectives.items()}
            comp = dict(self._cur_compile)
            comp["secs"] = round(comp.get("secs", 0.0), 9)
            wall0 = self._cur_iter_wall
            self._cur_iter = None
            self._cur_iter_wall = None
            self._counters["iterations"] = \
                self._counters.get("iterations", 0) + 1
            rec: Dict[str, Any] = {"ts": time.time(), "rank": self.rank,
                                   "event": "iteration", "iter": int(it),
                                   "sections": sections,
                                   "collectives": coll, "compile": comp}
            rec.update(attrs)
            self._events.append(rec)
            self._records.append(rec)
            sink = self._sink
        if sink is not None:
            sink.write(rec)
        if wall0 is not None:
            # enclosing span on the same track as the section spans, so
            # a trace viewer nests boosting/histogram_split/... inside it
            self.span("iteration", wall0, self.wall_now() - wall0,
                      track="train", iteration=it)
        return rec

    def megastep(self, it0: int, iterations: int, kept: int,
                 sections: Dict[str, float],
                 wall_start: Optional[float] = None,
                 **attrs: Any) -> Dict[str, Any]:
        """Batch-granularity training record: one megastep (or drained
        fast-path batch) covering iterations ``[it0, it0+iterations)``.
        The fast path cannot attribute per-section times without
        synchronizing every phase, so at ``telemetry_granularity=batch``
        wall time is attributed per drained batch instead — ``kept`` is
        how many of the batch's iterations survived the drain (a
        no-more-splits stop discards the tail). Counts toward the
        ``iterations`` counter like ``kept`` end_iteration calls and is
        queued for the record_telemetry callback."""
        if not self.enabled:
            return {}
        secs = {k: round(float(v), 9) for k, v in (sections or {}).items()}
        rec: Dict[str, Any] = {"ts": time.time(), "rank": self.rank,
                               "event": "megastep", "iter": int(it0),
                               "iterations": int(iterations),
                               "kept": int(kept), "sections": secs}
        rec.update(attrs)
        with self._lock:
            self._counters["iterations"] = \
                self._counters.get("iterations", 0) + int(kept)
            self._counters["events.megastep"] = \
                self._counters.get("events.megastep", 0) + 1
            for name, v in secs.items():
                self._observe_locked("section." + name, v)
            self._events.append(rec)
            self._records.append(rec)
            sink = self._sink
        if sink is not None:
            sink.write(rec)
        if wall_start is not None and secs:
            self.span("megastep", wall_start,
                      max(secs.values()), track="train", iteration=it0)
        return rec

    def restore_counters(self, counters: Dict[str, float]) -> None:
        """Seed the counter map from a checkpoint snapshot so a resumed
        run's dashboards continue instead of resetting (resilience/
        state.py). Saved values REPLACE current ones — restore happens
        before training resumes, when the registry is fresh."""
        if not counters:
            return
        with self._lock:
            for key, v in counters.items():
                try:
                    self._counters[str(key)] = float(v)
                except (TypeError, ValueError):
                    continue

    def drain_records(self) -> List[Dict[str, Any]]:
        """Completed iteration records since the last drain (the
        record_telemetry callback's feed)."""
        with self._lock:
            out = list(self._records)
            self._records.clear()
        return out

    # --------------------------------------------------------- snapshot
    def counters_snapshot(self) -> Dict[str, float]:
        """Counters alone — the cheap view the health auditor ships in
        its allgather payload (snapshot() copies the whole event ring,
        which a per-period collective should not)."""
        with self._lock:
            return dict(self._counters)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Counters/gauges/timings/dists WITHOUT the event rings — the
        exporter's per-scrape view (obs/export.py).  A busy serving
        process holds ~1500 event dicts in its rings; deep-copying them
        under the registry lock on every 15-second Prometheus scrape
        would contend with the batcher's hot-path ``event()`` calls for
        data the exposition never renders.  Dist ``count``/``sum`` are
        CUMULATIVE (monotone — what OpenMetrics summaries require);
        quantiles/min/max cover the bounded recent-sample ring."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "rank": self.rank,
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timings": {k: dict(v) for k, v in self._timings.items()},
                "dists": {k: self._dist_summary(v, self._dist_totals[k])
                          for k, v in self._dists.items() if v},
            }

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time dict view: counters, gauges, timing
        distributions and the recent event ring (rank-local; the
        end-of-training summary event carries the rank aggregate)."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "rank": self.rank,
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timings": {k: dict(v) for k, v in self._timings.items()},
                "dists": {k: self._dist_summary(v, self._dist_totals[k])
                          for k, v in self._dists.items() if v},
                "events": [dict(e) for e in self._events],
                "findings": [dict(e) for e in self._findings],
            }


def allgather_json(obj: Any) -> List[Any]:
    """SPMD allgather of one JSON-serializable value per rank (returns
    ``[obj]`` single-process).  Every rank must call this at the same
    point — the driver only does so from finalize_telemetry, which runs
    on all ranks by the SPMD contract."""
    import json as _json

    import jax
    import numpy as np

    if jax.process_count() <= 1:
        return [obj]
    from jax.experimental import multihost_utils

    from ..resilience.comms import guarded_call
    payload = np.frombuffer(_json.dumps(obj).encode("utf-8"), np.uint8)
    # guarded: with collective_timeout configured, a hung peer degrades
    # to a structured CollectiveError here instead of wedging this rank
    # inside the native allgather forever
    sizes = np.asarray(guarded_call(
        lambda: multihost_utils.process_allgather(
            np.asarray([payload.size], np.int64)),
        what="allgather_json/sizes")).reshape(-1)
    width = int(sizes.max())
    buf = np.zeros(width, np.uint8)
    buf[:payload.size] = payload
    gathered = np.asarray(guarded_call(
        lambda: multihost_utils.process_allgather(buf),
        what="allgather_json/payload")).reshape(sizes.size, width)
    return [_json.loads(bytes(gathered[r, :int(sizes[r])]).decode("utf-8"))
            for r in range(sizes.size)]
