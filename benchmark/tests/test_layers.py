"""Each per-layer reader on a run whose every number is known: the
hand-made trace of synthetic_trace.py and recorded facts. A reader that
finds nothing to read returns nothing."""
import types

import pytest

from harness import cells, context, trace_capture, trace_reduce as tr
from synthetic_trace import two_chunks, xspace


def fake_run(tmp_path, devices=1, traced=True, **facts):
    window = None
    if traced:
        path = tmp_path / "t.textproto"
        path.write_text(xspace(two_chunks(devices)))
        reduced = tr.reduce(str(path))
        mods = reduced.devices[0].modules
        step = tr.step_runs(reduced.devices[0])
        window = reduced.window(mods.start[step[0]], mods.start[step[1]])
    chip = types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"peak_bytes_in_use": 4_000})
    peer = types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"peak_bytes_in_use": 1_000})
    run = context.Run(
        cell={"name": "x", "chips": devices}, config={}, traffic={},
        seed=0, seconds=10.0, trace=traced, rehearsal=False, t_start=0.0,
        devices=[chip] + [peer] * (devices - 1), compile_log=None,
        scratch=str(tmp_path), window=window)
    run.phases.update(backend_init=9.5, bin=60.25)
    run.facts.update(
        t_train0=100.0, t_train1=160.5, t_dispatch0=120.0,
        t_last_chunk=160.0, megastep_cache_hit=True, dispatches=5,
        step_first_call_s=25.5,
        iterations=20, chunk_iterations=2, chips=devices,
        rows=1000 * devices, features=28, max_bin=63,
        tree_leaves=[255] * 20, tree_levels=[12] * 20,
        window_trees=[8, 9])
    if traced:
        run.facts["window_in_use_bytes"] = [1_500] + [300] * (devices - 1)
    run.facts.update(facts)
    return run


def read(name, run):
    return cells.load_module("layers", name).read(run)


@pytest.mark.parametrize("name,want", [
    ("entry.tail_s", 0.5),
    ("startup.backend_init_s", 9.5),
    ("startup.cache_hit", 1.0),
    ("startup.step_first_call_s", 25.5),
    ("data.bin_s", 60.25),
    ("data.upload_pack_s", 20.0),
    ("driver.dispatches_per_iter", 0.25),
    ("driver.chunk_gap_ms", 200 / 1e6),
    ("grower.kernel_launches_per_iter", 1.0),
    ("kernels.pallas_share", 100 * 400 / 900),
    # 2 trees x 2 x 1000 rows x 1792 x 5 x 255 operations at 197 TFLOP/s,
    # over 300 ns inside level_pass
    ("kernels.level_pass_roofline",
     100 * (2 * 2 * 1000 * 1792 * 5 * 255 / 197e12) / 300e-9),
    ("device.idle_share", 25.0),
    ("device.peak_hbm_bytes", 4000.0),
    ("device.window_hbm_bytes", 1500.0),
])
def test_reader_on_one_chip(tmp_path, name, want):
    assert read(name, fake_run(tmp_path)) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("collectives.time_share", 100 * 100 / 900),
    ("collectives.exposed_share", 100 * 100 / 900),
    ("device.hbm_imbalance", 4.0),
    ("device.idle_share", 25.0),
])
def test_reader_on_four_chips(tmp_path, name, want):
    assert read(name, fake_run(tmp_path, devices=4)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "driver.chunk_gap_ms", "grower.kernel_launches_per_iter",
    "kernels.pallas_share", "kernels.level_pass_roofline",
    "collectives.time_share", "collectives.exposed_share",
    "device.idle_share", "device.window_hbm_bytes"])
def test_a_trace_reader_without_a_trace_returns_nothing(tmp_path, name):
    assert read(name, fake_run(tmp_path, traced=False)) is None


@pytest.mark.parametrize("name", ["collectives.time_share",
                                  "collectives.exposed_share",
                                  "device.hbm_imbalance"])
def test_cross_chip_readers_return_nothing_on_one_chip(tmp_path, name):
    assert read(name, fake_run(tmp_path)) is None



def test_chunk_trace_keeps_the_most_each_device_held(tmp_path):
    sizes = iter([100, 700, 300])
    chip = types.SimpleNamespace(
        memory_stats=lambda: {"bytes_in_use": next(sizes)})
    silent = types.SimpleNamespace(memory_stats=lambda: None)   # the CPU
    follower = trace_capture.ChunkTrace(
        str(tmp_path / "telemetry.jsonl"), str(tmp_path / "trace"),
        start_after=1, stop_after=2, devices=[chip, silent])
    for _ in range(3):
        follower._look_at_memory()
    assert follower.in_use_peak == [700, 0]
