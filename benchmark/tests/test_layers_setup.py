"""The nine readers that open ``setup_s`` on a stream whose every number
is known: the program's ``setup_span`` and ``megastep`` events, written
by hand. A program without the spans gives them nothing to read."""
import types

import pytest

from harness import cells, setup_spans

JOB = "j1"


def span(name, t0, dur, parent=None, job=JOB, **attrs):
    return {"ts": t0 + dur, "rank": 0, "event": "setup_span", "name": name,
            "t0": float(t0), "dur_s": float(dur), "parent": parent,
            "job": job, **attrs}


def mega(ts, iterations=2):
    return {"ts": float(ts), "rank": 0, "event": "megastep",
            "iterations": iterations}


def stream():
    """One job with two step bodies, as the GOSS cell's: ``train`` from
    100 to 200, two warm-up chunks ending at 150 and 170, two measured
    ones; the second body's first call starts at 151, inside the set-up;
    a third first call at 175 is outside it."""
    return [
        span("bin/sample", 80, .5, "bin"), span("bin/mappers", 80.5, 1.5, "bin"),
        span("bin/rows", 82, 6, "bin"), span("bin/finalize", 88, .25, "bin"),
        span("bin", 80, 8.5),
        span("bin/rows", 90, 2, "bin"), span("bin", 90, 2.25),
        span("init/config_objective", 100, 1, "train/booster_init"),
        span("init/meta", 101, .5, "train/booster_init"),
        span("init/upload", 102, 4, "train/booster_init", bytes=10),
        span("init/pack", 106, 3, "train/booster_init"),
        span("init/reshard", 109, 1.5, "train/booster_init"),
        span("train/booster_init", 100, 11, "train"),
        span("valid/upload", 111, .5, "train/valid_sets"),
        span("valid/pack", 111.5, .25, "train/valid_sets"),
        span("train/valid_sets", 111, 1, "train"),
        span("first_call/build", 112, 1, "train"),
        # the first body: an inner jit's trace inside the step's own
        span("first_call/trace", 114, 2, "first_call/trace",
             fun_name="level_pass"),
        span("first_call/trace", 113, 10, "first_call", fun_name="step"),
        span("first_call/lower", 123, 5, "first_call"),
        span("first_call/load", 128, 12, "first_call", cache="hit"),
        span("first_call", 113, 28, "train", signature="a"),
        mega(150),
        # the second body, inside the second warm-up chunk
        span("first_call/trace", 151, 4, "first_call"),
        span("first_call/lower", 155, 2, "first_call"),
        span("first_call/load", 157, 3, "first_call", cache="miss"),
        span("first_call", 151, 10, "train", signature="b"),
        mega(170),
        # a third, after the set-up
        span("first_call/trace", 175, 1, "first_call"),
        span("first_call", 175, 2, "train", signature="c"),
        mega(180), mega(190),
        span("finish/drain", 190.5, .5, "finish"),
        span("finish/summary", 191, 2, "finish"),
        span("finish/drain", 193, .25, "finish"),
        span("finish", 190.5, 4, "train"),
        span("train", 100, 100),
    ]


def fake_run(events, warm=2):
    return types.SimpleNamespace(events=events,
                                 traffic={"warmup_chunks": warm})


def read(name, run):
    return cells.load_module("layers", name).read(run)


# leaves under [100, 170]: 1 + .5 + 4 + 3 + 1.5, .5 + .25, 1; of the first
# call 2 + 5 + 12 (its trace holds an inner jit's, which alone is the leaf
# there); of the second 4 + 2 + 3; the chunks' own 141..150 and 161..170
COVERED = 10 + .75 + 1 + 19 + 9 + 9 + 9


@pytest.mark.parametrize("name,want", [
    ("startup.step_trace_s", 10 + 4),
    ("startup.step_lower_s", 5 + 2),
    ("startup.step_load_s", 12 + 3),
    ("data.upload_s", 4 + .5),
    ("data.pack_s", 3 + 1.5 + .25),
    ("data.bin_rows_s", 6 + 2),
    ("entry.tail_drain_s", .5 + .25),
    ("entry.tail_finalize_s", 4 - .75),
    ("entry.setup_coverage", 100 * COVERED / 70),
])
def test_reader_on_the_hand_made_stream(name, want):
    assert read(name, fake_run(stream())) == pytest.approx(want)


NINE = ["startup.step_trace_s", "startup.step_lower_s",
        "startup.step_load_s", "data.upload_s", "data.pack_s",
        "data.bin_rows_s", "entry.tail_drain_s", "entry.tail_finalize_s",
        "entry.setup_coverage"]


@pytest.mark.parametrize("name", NINE)
def test_nothing_to_read_without_the_spans(name):
    """The parent's program writes ``megastep`` events and no span; a run
    that died before its first chunk writes neither."""
    assert read(name, fake_run([mega(150), mega(170), mega(180)])) is None
    assert read(name, fake_run([])) is None


def test_one_warm_up_chunk_takes_one_first_call():
    run = fake_run(stream(), warm=1)
    assert read("startup.step_trace_s", run) == pytest.approx(10)
    assert read("startup.step_load_s", run) == pytest.approx(12)
    # [100, 150]: the leaves up to the first call's and one chunk
    assert read("entry.setup_coverage", run) \
        == pytest.approx(100 * (10 + .75 + 1 + 19 + 9) / 50)


def test_only_the_last_job_of_a_stream_counts():
    older = [span("train", 10, 5, job="j0"),
             span("init/upload", 11, 3, "train/booster_init", job="j0"),
             span("bin/rows", 1, 9, "bin", job="j0")]
    run = fake_run(older + stream())
    assert read("data.upload_s", run) == pytest.approx(4.5)
    assert read("data.bin_rows_s", run) == pytest.approx(8)


def test_the_helpers():
    assert setup_spans.union_s([(0, 2), (1, 3), (5, 9)], 0, 6) == 4
    assert setup_spans.union_s([], 0, 6) == 0
    found = setup_spans.spans(fake_run(stream()))
    leaves = {(s["name"], s["t0"]) for s in setup_spans.leaves(found)}
    assert ("first_call/trace", 114.0) in leaves        # the inner jit's
    assert ("first_call/trace", 113.0) not in leaves    # holds it
    assert ("first_call", 113.0) not in leaves and ("bin", 80.0) not in leaves
    assert setup_spans.warmup_chunks(fake_run(stream())) \
        == [(141.0, 150.0), (161.0, 170.0)]
    # a chunk without a first call of its own starts where the last ended
    later = [e for e in stream() if e.get("signature") != "b"]
    assert setup_spans.warmup_chunks(fake_run(later)) \
        == [(141.0, 150.0), (150.0, 170.0)]
