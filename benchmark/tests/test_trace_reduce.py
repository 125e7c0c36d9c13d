"""The trace reduction against a hand-made trace whose numbers are known
and against a small trace recorded on the chip."""
import gzip
import os

import numpy as np
import pytest

from conftest import BENCH
from harness import trace_reduce as tr
from synthetic_trace import hlo, two_chunks, xspace

RECORDED = os.path.join(BENCH, "tests", "data", "v5e_train_chunk.textproto.gz")


@pytest.fixture()
def reduced(tmp_path):
    def make(planes):
        path = tmp_path / "t.textproto"
        path.write_text(xspace(planes))
        return tr.reduce(str(path))
    return make


def test_busy_is_the_union_of_leaf_operations(reduced):
    r = reduced(two_chunks())
    dev = r.devices[0]
    assert [dev.ops.name(i) for i in np.flatnonzero(~dev.ops.leaf)] == \
        ["while.3", "while.3"]
    step = tr.step_runs(dev)
    assert [dev.modules.name(i) for i in step] == ["jit_step(7)"] * 2
    w = r.window(dev.modules.start[step[0]], dev.modules.start[step[1]])
    assert (w.t0, w.t1) == (1000, 2200)
    assert w.busy_ns(dev) == 900                    # the while is no leaf
    assert w.gaps(dev) == [(1800, 1900), (2000, 2200)]
    pallas = dev.ops.where(tr.is_pallas)
    coll = dev.ops.where(tr.is_collective)
    assert w.busy_ns(dev, pallas) == 400 and w.count(dev, pallas) == 2
    assert {tr.kernel_of(op) for op in dev.ops.ops if tr.is_pallas(op)} \
        == {"level_pass", "table_lookup"}
    assert w.busy_ns(dev, coll) == 100
    assert w.collective_ns(dev) == (100, 100)
    assert dict(w.totals(dev)) == pytest.approx({
        "fusion.1": 3e-7, "level_pass.2": 3e-7, "table_lookup.9": 1e-7,
        "all-reduce.4": 1e-7, "custom-call.5": 1e-7})
    assert r.steps == [(1001, 8)]
    # the gap is labelled by the innermost event of the program's thread
    # that covers most of it, never by the harness's polling thread
    assert w.host_label(2000, 2200) == "$api.py:2 device_get"


def test_events_are_clipped_to_the_window(reduced):
    r = reduced(two_chunks())
    dev = r.devices[0]
    w = r.window(1200, 1500)                        # inside two operations
    assert w.busy_ns(dev) == 300 and w.gaps(dev) == []
    assert w.count(dev, dev.ops.where(tr.is_pallas)) == 1   # starts at 1300


def test_an_operation_is_named_by_its_instruction():
    op = tr.parse_op(
        '%level_pass.25 = (f32[1792,40]{1,0:T(8,128)S(1)}, s32[1,28000256]'
        '{1,0:T(1,128)}) custom-call(s8[28,28000256]{1,0:T(8,128)(4,1)} '
        '%get-tuple-element.192, s32[8,128]{1,0:T(8,128)S(1)} %dus.174), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s8[28,28000256]{1,0}}')
    assert op == ("level_pass.25", "custom-call", "tpu_custom_call")
    assert tr.is_pallas(op) and tr.kernel_of(op) == "level_pass"
    op = tr.parse_op("%all-reduce-start.3 = f32[1792,40]{1,0:T(8,128)} "
                     "all-reduce-start(f32[1792,40]{1,0:T(8,128)} %f.1), "
                     "replica_groups={{0,1,2,3}}, to_apply=%add")
    assert op.opcode == "all-reduce-start" and tr.is_collective(op)
    assert tr.parse_op("$gbdt.py:3752 _drain_body") == \
        ("$gbdt.py:3752 _drain_body", "", "")


def test_overlapping_operations_are_not_counted_twice(reduced):
    planes = two_chunks()
    # an asynchronous gather that runs under the fusion, and its wait
    planes["/device:TPU:0"]["Async XLA Ops"].append(
        (hlo("all-gather-start.9", "all-gather-start"), 1250, 600))
    r = reduced(planes)
    dev = r.devices[0]
    w = r.window(1000, 2200)
    assert w.busy_ns(dev) == 900
    # 1250..1850 async + 1700..1800 sync, of which only the sync
    # all-reduce and the 50 ns that reach into the idle stretch are not
    # under another operation
    assert w.collective_ns(dev) == (600, 150)


def test_every_chip_has_its_plane_in_order(reduced):
    r = reduced(two_chunks(devices=4))
    assert [d.name for d in r.devices] == [f"/device:TPU:{i}"
                                           for i in range(4)]


def test_a_trace_without_a_device_plane_is_an_error(reduced):
    planes = {k: v for k, v in two_chunks().items() if "TPU" not in k}
    with pytest.raises(tr.NoDevicePlane, match="no plane /device:TPU:"):
        reduced(planes)
    planes["/device:TPU:0"] = {"Steps": [("7", 0, 10)]}
    with pytest.raises(tr.NoDevicePlane, match="XLA Ops"):
        reduced(planes)


def test_no_trace_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError, match="wrote nothing"):
        tr.find_xplane(str(tmp_path))


def test_the_recorded_chip_trace_reads_as_it_did():
    """A 0.4 s slice of a traced run of the one-chip train job (at 28M
    rows) on a TPU v5e (my chip run, PR 22): the end of one chunk, the drain, the start of
    the next; device lines XLA Ops / Async XLA Ops / XLA Modules, the
    program's host thread. Guards what only the chip shows: the plane
    and line names, HLO text as event names, the nesting, the kernels'
    names, one clock for host and device."""
    r = tr.reduce(RECORDED)
    assert [d.name for d in r.devices] == ["/device:TPU:0"]
    dev = r.devices[0]
    assert (len(dev.ops), len(dev.async_ops), len(dev.modules)) == \
        (361, 42, 16)
    assert {dev.ops.ops[i].opcode for i in dev.ops.op_id[~dev.ops.leaf]} \
        == {"while"}
    assert {tr.kernel_of(op) for op in dev.ops.ops if tr.is_pallas(op)} \
        == {"level_pass", "table_lookup"}
    assert not any(tr.is_collective(op) for op in dev.ops.ops)
    step = tr.step_runs(dev)
    assert step.size == 4 and dev.modules.name(step[0]).startswith("jit_step(")
    # the step annotation of the chunk that starts at iteration 12 sits
    # within a millisecond of its run on the device
    t0 = int(dev.modules.start[step[2]])
    assert r.steps == [(21619013614, 12)] and abs(r.steps[0][0] - t0) < 1e6
    gap = (int(dev.modules.end[step[1]]), t0)
    assert gap[1] - gap[0] == 18343182
    w = r.window(t0, t0 + 170_000_000)
    assert w.host_label(*gap) == "$gbdt.py:121 __init__"
    pallas = dev.ops.where(tr.is_pallas)
    assert w.count(dev, pallas) == 1
    assert w.busy_ns(dev, pallas) == 153893257      # level_pass.36, clipped
    assert w.busy_ns(dev) == pytest.approx(0.1699e9, rel=1e-3)
    assert w.totals(dev)[0][0] == "level_pass.36"
