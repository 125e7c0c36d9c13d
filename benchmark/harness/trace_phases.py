"""Whose the device's time is: the program's ``lgbm.*`` phase scopes read
back from the profiler's trace, and its host sections inside a window.

The program names the phases of its traced step with ``jax.named_scope``
(``lgbm.gradients``, ``lgbm.grow`` > ``level`` > ``hist`` ...;
docs/Observability.md section 3a), which XLA keeps as each instruction's
``op_name``. On the chip the profiler writes that name into the trace
itself: every distinct operation of a device plane has an
``XEventMetadata`` whose *stats* hold ``tf_op`` (the ``op_name`` and a
colon) and ``source`` (``file:line``). ``jax.profiler.ProfileData`` shows
an event's own stats only, not its metadata's, so this module reads those
two stats from the file's bytes with a protobuf wire-format reader of its
own (the five messages of ``xplane.proto`` it needs; lines and events are
skipped unread). Found on the chip in step 0 of issue 25: no derived
``Framework Name Scope`` line in the raw ``.xplane.pb``, the HLO protos
are embedded too (plane ``/host:metadata``) but the stats make reading
them unnecessary, and nothing has to be added to the program's telemetry.

A fusion carries ONE name, its root instruction's; an instruction the
compiler made itself carries none and counts as ``unscoped``: it is not
guessed. Times come from ``trace_reduce``: the busy time of LEAF
operations, each instant counted once, so the phases partition
``Window.busy_ns`` exactly.
"""
from __future__ import annotations

import functools
import gzip
import os
from collections import namedtuple

import numpy as np

from . import trace_reduce

PREFIX = "lgbm."
UNSCOPED = "unscoped"
# the vocabulary: one flat level, and one level of nesting in the grower
GROW_STAGES = ("root", "level")
LEVEL_STAGES = ("route", "hist", "split", "book")
# what XLA makes for a level's ``cond``s themselves (copies of the carries
# in and out, the conditional whose pass-through branch ran) is named after
# the ``cond``, in no stage: all that a skipped level costs
SKIP = "skip"
# the top-level phases that the two driver shares add up
UPDATE = ("gradients", "gh_pack", "score_update", "freeze")
EVAL = ("valid_apply", "eval", "early_stop")
DRAIN_SECTIONS = "GBDT::Drain::"
DRAIN_WAIT = "GBDT::Drain::Fetch"      # waiting for the device: no host work

# what the trace says of one instruction
Named = namedtuple("Named", "op_name source")


def phase_of(op_name: str) -> str:
    """``jit(step)/while/body/closed_call/lgbm.grow/jit(grow_tree_fused)/
    level/cond/branch_1_fun/hist/mul`` -> ``grow/level/hist``. The
    innermost ``lgbm.`` scope counts (the lookup kernel inside the
    grower's shard_map region is ``score_update``); the last component is
    the primitive, never a scope; transformations (``jit(f)``, ``cond``,
    ``branch_1_fun``) between two scopes are skipped."""
    at = op_name.rfind(PREFIX)
    if at < 0:
        return UNSCOPED
    parts = op_name[at + len(PREFIX):].split("/")
    top, inner = parts[0], parts[1:-1]
    if top == "grow":
        stage = next((p for p in inner if p in GROW_STAGES), None)
        if stage is None:
            return top
        if stage == "level":
            sub = next((p for p in inner[inner.index("level") + 1:]
                        if p in LEVEL_STAGES), None)
            if sub is None and parts[-1] == "cond":
                sub = SKIP
            if sub is not None:
                return f"{top}/level/{sub}"
        return f"{top}/{stage}"
    if top == "eval" and inner and "(" not in inner[0]:
        return f"{top}/{inner[0]}"           # the metric: eval/auc
    return top


def top_phase(phase: str) -> str:
    return phase.split("/", 1)[0]


# ---- the trace file's bytes -------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}: not an XSpace")
        yield key >> 3, value


def _map_value(entry):
    """The value of a ``map<int64, Message>`` entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _plane_names(plane) -> tuple:
    """(plane name, {instruction name: Named}) from an ``XPlane``:
    ``name`` = 2, ``event_metadata`` = 4, ``stat_metadata`` = 5."""
    name, events, stat_names = "", [], {}
    for field, value in _fields(plane):
        if field == 2:
            name = bytes(value).decode()
        elif field == 4:
            events.append(_map_value(value))
        elif field == 5:
            # XStatMetadata: id = 1, name = 2
            meta = dict(_fields(_map_value(value)))
            stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
    wanted = {i: n for i, n in stat_names.items() if n in ("tf_op", "source")}
    named = {}
    for meta in events:
        # XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
        # str_value = 5
        text, found = "", {}
        for field, value in _fields(meta):
            if field == 2:
                text = bytes(value).decode(errors="replace")
            elif field == 5:
                stat = dict(_fields(value))
                if stat.get(1) in wanted and 5 in stat:
                    found[wanted[stat[1]]] = bytes(stat[5]).decode(
                        errors="replace")
        if "tf_op" in found:
            # "<op_name>:<op type>", the type empty for XLA's own
            op_name = found["tf_op"].rpartition(":")[0] or found["tf_op"]
            named[trace_reduce.parse_op(text).name] = Named(
                op_name, found.get("source", ""))
    return name, named


def _read(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        blob = fh.read()
    if ".textproto" in os.path.basename(path):
        from jax.profiler import ProfileData
        return ProfileData.text_proto_to_serialized_xspace(blob.decode())
    return blob


@functools.lru_cache(maxsize=2)
def names(path: str) -> dict:
    """{device plane name: {instruction name: Named}} of the trace at
    ``path`` (the forms ``trace_reduce.load`` takes). A plane without the
    stats (a CPU trace, a trace of the parent commit's program has the
    stats but no ``lgbm.`` in them) gives an empty or scope-less map,
    never an error."""
    out = {}
    for field, plane in _fields(memoryview(_read(path))):
        if field == 1:                                  # XSpace.planes
            name, named = _plane_names(plane)
            if name.startswith(trace_reduce.DEVICE_PLANE):
                out[name] = named
    return out


# ---- device time by phase ---------------------------------------------------

def _own_ns(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each interval's part of the union (sorted by start): what it adds
    beyond everything that started before it. Sums to ``_union_ns``."""
    if start.size == 0:
        return np.zeros(0, np.int64)
    reach = np.r_[start[0], np.maximum.accumulate(end)[:-1]]
    return np.clip(end - np.maximum(start, reach), 0, None)


def busy_by_op(window, dev) -> dict:
    """{instruction name: (busy ns, launches)} of the leaf operations of
    ``dev`` inside ``window``; the ns sum to ``window.busy_ns(dev)``."""
    ops = dev.ops
    start, end, keep = window._clip(ops, ops.leaf)
    own = _own_ns(start, end)
    ids = ops.op_id[keep]
    ns = np.bincount(ids, weights=own, minlength=len(ops.ops))
    began = np.bincount(ids[ops.start[keep] >= window.t0],
                        minlength=len(ops.ops))
    return {ops.ops[i].name: (int(ns[i]), int(began[i]))
            for i in np.flatnonzero((ns > 0) | (began > 0))}


def phased_ops(window, dev, named: dict) -> list:
    """[(phase, is a Pallas kernel, instruction name, busy ns, launches,
    its Named or None)] of the leaf operations of one device inside
    ``window``. ``named`` is that device's map from ``names``."""
    pallas = {op.name for op in dev.ops.ops if trace_reduce.is_pallas(op)}
    out = []
    for name, (ns, launches) in busy_by_op(window, dev).items():
        known = named.get(name)
        out.append((phase_of(known.op_name) if known else UNSCOPED,
                    name in pallas, name, ns, launches, known))
    return out


def busy_by_phase(window, dev, named: dict) -> dict:
    """{(phase, is a Pallas kernel): busy ns} for one device."""
    out = {}
    for phase, pallas, _, ns, _, _ in phased_ops(window, dev, named):
        out[phase, pallas] = out.get((phase, pallas), 0) + ns
    return out


def of_run(run):
    """[(device, {(phase, is Pallas): ns})] for the traced window of a
    run, or None: no window (an untraced run, a rehearsal off the chip),
    or a program that names no phase (the parent commit of the PR that
    brought the scopes), so that a reader reports nothing, not zero."""
    if run.window is None:
        return None
    path = trace_reduce.find_xplane(os.path.join(run.scratch, "trace"))
    named = names(path)
    if not any(PREFIX in n.op_name for plane in named.values()
               for n in plane.values()):
        return None
    return [(dev, busy_by_phase(run.window, dev, named.get(dev.name, {})))
            for dev in run.window.reduced.devices]


def share(run, pick):
    """Per cent of busy time, mean over the chips, of the (phase, is
    Pallas) keys that ``pick`` accepts; None where ``of_run`` is."""
    table = of_run(run)
    if table is None:
        return None
    shares = [sum(ns for key, ns in by.items() if pick(*key))
              / run.window.busy_ns(dev) for dev, by in table]
    return 100.0 * sum(shares) / len(shares)


# ---- the host's sections ----------------------------------------------------

def chunk_boundaries(reduced) -> list:
    """[(t0, t1)]: on the first chip, from the end of each run of the
    training step to the start of the next."""
    dev = reduced.devices[0]
    step = trace_reduce.step_runs(dev)
    return [(int(dev.modules.end[a]), int(dev.modules.start[b]))
            for a, b in zip(step[:-1], step[1:])]


def host_section_ns(reduced, t0: int, t1: int, pick) -> dict:
    """{section name: ns inside [t0, t1)} of the program's own threads,
    for the sections (``TraceAnnotation`` names of ``utils/timer``) that
    ``pick`` accepts; nested repeats of one name count once."""
    out = {}
    for line in reduced.program_threads():
        for i, op in enumerate(line.ops):
            if not pick(op.name):
                continue
            hit = (line.op_id == i) & (line.end > t0) & (line.start < t1)
            ns = trace_reduce._union_ns(np.clip(line.start[hit], t0, t1),
                                        np.clip(line.end[hit], t0, t1))
            if ns:
                out[op.name] = out.get(op.name, 0) + ns
    return out


def is_drain_work(section: str) -> bool:
    return section.startswith(DRAIN_SECTIONS) and section != DRAIN_WAIT
