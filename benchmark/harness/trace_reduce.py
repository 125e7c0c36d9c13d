"""From the profiler's ``.xplane.pb`` to what the per-layer readers use.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. A
device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per executed HLO operation, named by the instruction's whole
text (``%level_pass.31 = (...) custom-call(...), custom_call_target=
"tpu_custom_call"``), its ``Async XLA Ops`` line the spans of asynchronous
copies and collectives, and its ``XLA Modules`` line one event per
executable run. Operations nest (a ``while`` or a ``conditional`` spans
its body), so busy time is the union of LEAF operations: events that
contain no other event of the same line. A trace without a device plane
is an error, never an empty result: a CPU trace must not read as an idle
device.

All times are nanoseconds on the trace's own clock, shared by the device
and host planes.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import namedtuple
from dataclasses import dataclass

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS_TARGET = "tpu_custom_call"
HOST_PLANE = "/host:CPU"
STEP_ANNOTATION = "megastep"    # the program's StepTraceAnnotation
# HLO names of cross-chip operations (psum lowers to all-reduce)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


class NoDevicePlane(RuntimeError):
    """The trace holds no TPU plane, or the plane no operations."""


# One distinct event name of a line. For an HLO operation: the
# instruction's name (``level_pass.31``), its opcode (``custom-call``) and
# its custom-call target; for any other event the name alone.
Op = namedtuple("Op", "name opcode target")
_HLO = re.compile(r"^%(?P<name>[^ ]+) = .*?[\]\)\}] (?P<opcode>[a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def parse_op(text: str) -> Op:
    m = _HLO.match(text)
    if not m:
        return Op(text, "", "")
    target = _TARGET.search(text) if m["opcode"] == "custom-call" else None
    return Op(m["name"], m["opcode"], target.group(1) if target else "")


@dataclass
class Line:
    """Events of one trace line, sorted by start."""
    start: np.ndarray                      # int64 ns
    end: np.ndarray                        # int64 ns
    op_id: np.ndarray                      # int32 into ops
    ops: list                              # [Op], distinct
    leaf: np.ndarray                       # bool: contains no other event

    def __len__(self) -> int:
        return int(self.start.size)

    def name(self, i: int) -> str:
        return self.ops[int(self.op_id[i])].name

    def where(self, pred) -> np.ndarray:
        """Mask of the events whose ``Op`` satisfies ``pred``."""
        hit = np.fromiter((bool(pred(op)) for op in self.ops), bool,
                          len(self.ops))
        return hit[self.op_id] if len(self) else np.zeros(0, bool)


@dataclass
class Device:
    name: str
    ops: Line
    async_ops: Line
    modules: Line


@dataclass
class Reduced:
    devices: list                           # [Device], by name
    host: dict                              # "line name #n" -> Line
    steps: list                             # [(start ns, step_num)] of
    #                                         the program's annotation

    def window(self, t0: int, t1: int) -> "Window":
        return Window(self, int(t0), int(t1))

    def program_threads(self) -> list:
        """The host threads that drive the program: those that carry its
        ``megastep`` step annotation, else every thread (the harness's
        own trace thread only polls and would label gaps 'sleep')."""
        marked = [ln for ln in self.host.values()
                  if any(op.name == STEP_ANNOTATION for op in ln.ops)]
        return marked or list(self.host.values())


def _line(events) -> Line:
    names, ids, start, dur = {}, [], [], []
    for e in events:
        ids.append(names.setdefault(e.name, len(names)))
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    ops = [parse_op(n) for n in names]
    start = np.asarray(start, np.float64)
    end = start + np.asarray(dur, np.float64)
    ids = np.asarray(ids, np.int32)
    # parents before their children: by start, longer first
    order = np.lexsort((-end, start))
    start = np.rint(start[order]).astype(np.int64)
    end = np.rint(end[order]).astype(np.int64)
    return Line(start, end, ids[order], ops, _leaves(start, end))


def _leaves(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """True where an event contains no later-starting event. Events that
    merely overlap (asynchronous pairs) are both kept as leaves."""
    leaf = np.ones(start.size, bool)
    stack = []
    for i in range(start.size):
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack and end[i] <= end[stack[-1]] and (
                end[i] - start[i] < end[stack[-1]] - start[stack[-1]]):
            leaf[stack[-1]] = False
        stack.append(i)
    return leaf


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}: the "
                                "profiler wrote nothing")
    return found[-1]


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb``, a gzipped one, or a text
    proto (``.textproto[.gz]``, the form the recorded test trace keeps)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        blob = fh.read()
    if ".textproto" in os.path.basename(path):
        return ProfileData.from_text_proto(blob.decode())
    return ProfileData.from_serialized_xspace(blob)


def reduce(path: str) -> Reduced:
    """Device and host lines of the trace at ``path``."""
    profile = load(path)
    devices, host, steps, seen = [], {}, [], []
    for plane in profile.planes:
        seen.append(plane.name)
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise NoDevicePlane(
                    f"{plane.name} has no {OPS_LINE!r} line, only "
                    f"{sorted(lines)}")
            ops, other, mods = (
                _line(lines[n].events if n in lines else [])
                for n in (OPS_LINE, ASYNC_LINE, MODULES_LINE))
            if len(ops):
                devices.append(Device(plane.name, ops, other, mods))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                line = _line(e for e in ln.events if e.duration_ns > 0)
                if len(line):
                    # every Python thread's line is named "python3"
                    host[f"{ln.name} #{len(host)}"] = line
                steps += [(int(e.start_ns), int(dict(e.stats)["step_num"]))
                          for e in ln.events if e.name == STEP_ANNOTATION]
    if not devices:
        raise NoDevicePlane(
            f"no plane {DEVICE_PLANE}<n> with operations in {path}; "
            f"planes: {seen}. A trace taken off the chip has no device "
            "metric to give.")
    devices.sort(key=lambda d: int(d.name[len(DEVICE_PLANE):].split()[0]))
    return Reduced(devices, host, sorted(steps))


def reduce_dir(trace_dir: str, rehearsal: bool = False):
    """``reduce`` of the trace the profiler left under ``trace_dir``. Only
    a rehearsal (a CPU run) may lack a device plane: it gets ``None``, and
    the trace readers then return nothing."""
    try:
        return reduce(find_xplane(trace_dir))
    except NoDevicePlane:
        if not rehearsal:
            raise
        return None


def _union_ns(start: np.ndarray, end: np.ndarray) -> int:
    """Total length of the union of [start, end) intervals (sorted by
    start)."""
    if start.size == 0:
        return 0
    reach = np.maximum.accumulate(end)
    fresh = np.r_[True, start[1:] > reach[:-1]]
    first = np.flatnonzero(fresh)
    last = np.r_[first[1:] - 1, start.size - 1]
    return int((reach[last] - start[first]).sum())


class Window:
    """One stretch [t0, t1) of a reduced trace; every share below is over
    it, with events clipped to it."""

    def __init__(self, reduced: Reduced, t0: int, t1: int):
        if t1 <= t0:
            raise ValueError(f"empty trace window [{t0}, {t1})")
        self.reduced, self.t0, self.t1 = reduced, t0, t1

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clip(self, line: Line, mask=None):
        keep = (line.end > self.t0) & (line.start < self.t1)
        if mask is not None:
            keep &= mask
        return (np.clip(line.start[keep], self.t0, self.t1),
                np.clip(line.end[keep], self.t0, self.t1), keep)

    def busy_ns(self, dev: Device, mask=None) -> int:
        """Union of the leaf operations (optionally only those in
        ``mask``) inside the window."""
        leaf = dev.ops.leaf if mask is None else dev.ops.leaf & mask
        s, e, _ = self._clip(dev.ops, leaf)
        return _union_ns(s, e)

    def count(self, dev: Device, mask) -> int:
        """Leaf operations in ``mask`` that START inside the window."""
        ops = dev.ops
        return int((ops.leaf & mask & (ops.start >= self.t0)
                    & (ops.start < self.t1)).sum())

    def gaps(self, dev: Device) -> list:
        """Idle stretches [(start, end)] between leaf operations."""
        s, e, _ = self._clip(dev.ops, dev.ops.leaf)
        out, reach = [], self.t0
        for a, b in zip(s, e):
            if a > reach:
                out.append((int(reach), int(a)))
            reach = max(reach, int(b))
        if reach < self.t1:
            out.append((reach, self.t1))
        return out

    def collective_ns(self, dev: Device) -> tuple:
        """(time in cross-chip operations, the part of it during which no
        other leaf operation runs on that chip). A synchronous collective
        is a leaf of the operations line; an asynchronous one is its span
        on the asynchronous line."""
        coll = dev.ops.where(is_collective)
        s1, e1, _ = self._clip(dev.ops, dev.ops.leaf & coll)
        s2, e2, _ = self._clip(dev.async_ops,
                               dev.async_ops.where(is_collective))
        so, eo, _ = self._clip(dev.ops, dev.ops.leaf & ~coll)

        def union(starts, ends):
            start, end = np.concatenate(starts), np.concatenate(ends)
            order = np.argsort(start, kind="stable")
            return _union_ns(start[order], end[order])

        total = union([s1, s2], [e1, e2])
        return total, union([s1, s2, so], [e1, e2, eo]) - union([so], [eo])

    def totals(self, dev: Device) -> list:
        """[(operation name, seconds)] of leaf operations, clipped, by
        time."""
        s, e, keep = self._clip(dev.ops, dev.ops.leaf)
        ns = np.bincount(dev.ops.op_id[keep], weights=e - s,
                         minlength=len(dev.ops.ops))
        order = np.argsort(-ns)
        return [(dev.ops.ops[i].name, ns[i] / 1e9)
                for i in order if ns[i] > 0]

    def host_label(self, a: int, b: int) -> str:
        """What the host was doing in [a, b): the innermost (shortest)
        host event that covers at least half of it, else the one that
        covers most."""
        inner, outer = None, None       # (duration, name), (cover, name)
        for line in self.reduced.program_threads():
            lo = np.searchsorted(line.start, b)
            for i in np.flatnonzero(line.end[:lo] > a):
                cover = int(min(b, line.end[i]) - max(a, line.start[i]))
                dur = int(line.end[i] - line.start[i])
                if 2 * cover >= b - a and (inner is None or dur < inner[0]):
                    inner = (dur, line.name(i))
                if outer is None or cover > outer[0]:
                    outer = (cover, line.name(i))
        pick = inner or outer
        return pick[1] if pick else "host: nothing recorded"


def step_runs(dev: Device) -> np.ndarray:
    """Indices into ``dev.modules`` of the runs of the training step: the
    executable that holds most of the traced device time."""
    mods = dev.modules
    if not len(mods):
        raise RuntimeError(f"{dev.name} has no {MODULES_LINE!r} events: "
                           "cannot find the chunks")
    per_name = np.bincount(mods.op_id, weights=mods.end - mods.start)
    return np.flatnonzero(mods.op_id == int(np.argmax(per_name)))


def is_collective(op: Op) -> bool:
    return op.opcode.startswith(COLLECTIVES)


def is_pallas(op: Op) -> bool:
    """A Pallas (Mosaic) kernel launch: a custom call to the TPU's kernel
    target. XLA names the instruction after the jitted function that
    wraps the ``pallas_call`` (``level_pass.31``), which is how
    ``kernel_of`` tells the program's kernels apart."""
    return op.target == PALLAS_TARGET


def kernel_of(op: Op) -> str:
    """``level_pass.31`` -> ``level_pass``."""
    return op.name.rsplit(".", 1)[0]
