"""jax.monitoring bridge + per-device memory accounting.

JAX reports compile phases through ``jax.monitoring``
(``/jax/core/compile/jaxpr_trace_duration``,
``.../jaxpr_to_mlir_module_duration``, ``.../backend_compile_duration``):
each as a duration and as a time span with its true start and end.
Process-wide listeners are installed on first attach. The durations fan
out to every live, enabled :class:`Telemetry` as the ``compile.*``
counters and timings, so per-booster registries see the compiles their
iterations trigger (a recompile mid-training is exactly the kind of
cliff one-off timing scripts keep missing). The time spans go two ways:
to the ``compile`` track of ``trace_out``, with jax's own bounds, and,
while a step's ``first_call`` span is open on the calling thread
(registry.Span, ``adopt=True``), into that span as its children
``first_call/trace`` / ``lower`` / ``load`` with jax's ``fun_name``; the
two ``/jax/compilation_cache/*`` durations jax records on a cache hit
land on the ``first_call/load`` they precede.

Memory accounting covers EVERY local device, not just device 0: a
multi-chip host where one device's allocator is near its limit while
device 0 idles is precisely the failure per-device gauges exist to
show.  ``memory_watermarks`` snapshots ``bytes_in_use`` /
``peak_bytes_in_use`` / ``bytes_limit`` into per-device registry gauges
at the driver's natural sync points (megastep drain, serving dispatch)
so the OpenMetrics exporter can expose live HBM headroom.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional

_COMPILE_PREFIX = "/jax/core/compile"
# the children of a step's first call, by jax's phase
_PHASE_SPAN = {"jaxpr_trace_duration": "first_call/trace",
               "jaxpr_to_mlir_module_duration": "first_call/lower",
               "backend_compile_duration": "first_call/load"}
# what jax records on a persistent-cache hit, inside the backend phase
_CACHE_ATTR = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved_s"}

_lock = threading.Lock()
_installed = False
_active: "weakref.WeakSet" = weakref.WeakSet()

# backends whose devices report no allocator stats (CPU, interpret)
# answer None once and are never re-queried: the watermark hook sits on
# the serving dispatch path, where a per-batch jax.local_devices() walk
# that can only ever return None is pure overhead
_mem_unsupported = False


def attach(tel) -> None:
    """Subscribe a Telemetry instance to compile events (idempotent)."""
    global _installed
    with _lock:
        _active.add(tel)
        if _installed:
            return
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_time_span_listener(_on_time_span)
        except Exception:  # monitoring API unavailable: degrade silently
            pass
        _installed = True


def detach(tel) -> None:
    with _lock:
        _active.discard(tel)


# parameter names of Telemetry.span that a monitoring kwarg must never
# shadow — a colliding key would raise TypeError INSIDE jax's compile
# path and kill the jit that triggered the listener
_RESERVED_ATTRS = frozenset(
    {"phase", "seconds", "name", "track", "iteration", "wall_start",
     "event", "duration", "start", "end", "t0", "dur_s", "parent", "job"})


def _identity(kwargs: dict) -> dict:
    """Only plain scalar identity attrs survive — the record must stay
    JSON- and trace-serializable whatever jax adds to the callback."""
    return {k: v for k, v in kwargs.items()
            if isinstance(v, (str, int, float, bool))
            and k not in _RESERVED_ATTRS}


def _adopting_span():
    """The innermost open span of this thread that adopts jax's time
    spans (a step's ``first_call``), or None."""
    from .registry import open_spans
    for span in reversed(open_spans()):
        if span.adopts:
            return span
    return None


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event in _CACHE_ATTR:
        span = _adopting_span()
        if span is not None:
            span.cache[_CACHE_ATTR[event]] = round(float(duration), 6)
        return
    if not event.startswith(_COMPILE_PREFIX):
        return
    # short phase name: "backend_compile_duration" etc.
    phase = event.rsplit("/", 1)[-1]
    for tel in list(_active):
        if tel.enabled:
            try:
                tel.compile_event(phase, float(duration))
            except Exception:
                # a telemetry bug must never propagate out of the
                # monitoring listener into the XLA compile it observes
                pass


def _on_time_span(event: str, start: float, end: float, **kwargs) -> None:
    if not event.startswith(_COMPILE_PREFIX):
        return
    phase = event.rsplit("/", 1)[-1]
    try:
        attrs = _identity(kwargs)
        for tel in list(_active):
            if tel.enabled:
                tel.compile_span(phase, float(start), float(end), **attrs)
        span = _adopting_span()
        name = _PHASE_SPAN.get(phase)
        if span is None or name is None:
            return
        if phase == "backend_compile_duration":
            # key, cache read, deserialise and load; or the compile
            hit, span.cache = span.cache, {}
            attrs = dict(attrs, cache="hit" if hit else "miss", **hit)
        span.adopt(name, start, end, **attrs)
    except Exception:
        pass    # as above: never into the compile it observes


_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
              "largest_alloc_size", "bytes_reserved",
              "peak_bytes_reserved", "largest_free_block_bytes")

#: stats exported as per-device gauges by memory_watermarks (the
#: reserved-bytes pair only exists where the backend's allocator
#: reports it — TPU/GPU BFC allocators do, CPU does not; absent keys
#: are simply absent from the gauges, never zero-filled)
_GAUGE_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
               "bytes_reserved", "peak_bytes_reserved")


def device_memory_stats() -> Optional[Dict[int, dict]]:
    """Allocator stats of EVERY local device, keyed by device id
    (``{0: {"bytes_in_use": ..., ...}, 1: {...}}``).  Backends whose
    devices report nothing (CPU, interpret) return None — cleanly, and
    cached so repeated polling costs one attribute check."""
    global _mem_unsupported
    if _mem_unsupported:
        return None
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return None
    out: Dict[int, dict] = {}
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        ent = {key: int(ms[key]) for key in _STAT_KEYS if key in ms}
        if ent:
            out[int(getattr(d, "id", len(out)))] = ent
    if not out:
        _mem_unsupported = True
        return None
    return out


def memory_watermarks(tel, where: str = "") -> Optional[Dict[int, dict]]:
    """Gauge every local device's live and peak allocator bytes into the
    registry (``mem.d<id>.bytes_in_use`` / ``.peak_bytes_in_use`` /
    ``.bytes_limit``) and count the observation under
    ``mem.watermarks.<where>``.  Called at megastep drain and serving
    dispatch boundaries — the two places the allocator's peak actually
    moves — so the exporter's HBM-headroom gauges track the run live.
    Returns the per-device stats (None where unsupported)."""
    if tel is None or not tel.enabled:
        return None
    stats = device_memory_stats()
    if not stats:
        return None
    for did, ent in stats.items():
        for key in _GAUGE_KEYS:
            if key in ent:
                tel.gauge(f"mem.d{did}.{key}", ent[key])
        frag = fragmentation(ent)
        if frag is not None:
            ent["fragmentation"] = frag
            tel.gauge(f"mem.d{did}.fragmentation", frag)
    if where:
        tel.inc("mem.watermarks." + where)
    return stats


def fragmentation(ent: dict) -> Optional[float]:
    """Free-space fragmentation of one device's allocator: the share of
    free pool bytes NOT reachable as a single contiguous block
    (``1 - largest_free_block / free``).  0 = one perfect free block;
    approaching 1 = free space is shattered and a large histogram
    buffer may OOM despite headroom.  ``largest_free_block_bytes``
    describes the allocator's RESERVED pool, so where the allocator
    reports ``bytes_reserved`` (a growing BFC pool) the free
    denominator is ``bytes_reserved - bytes_in_use`` — dividing by the
    whole unreserved limit would read a barely-grown pool as ~100%
    fragmented while most of HBM is freely allocatable.  None where
    the backend reports no block/limit stats (CPU)."""
    try:
        in_use = int(ent["bytes_in_use"])
        largest = int(ent["largest_free_block_bytes"])
        pool = int(ent.get("bytes_reserved", ent["bytes_limit"]))
    except (KeyError, TypeError, ValueError):
        return None
    free = pool - in_use
    if free <= 0:
        return 0.0
    return max(0.0, min(1.0, 1.0 - largest / free))
