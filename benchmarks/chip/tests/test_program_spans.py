"""The readers of the program's spans on synthetic span lists: a program
span counts toward the benchmark span it lies inside, each reader averages
over that benchmark span's occurrences, and a run without program spans
reads None."""
import pytest

import bench
import program_spans

METRICS = bench.HERE / "metrics"


def reader(name):
    return bench.load_module(METRICS / f"{name}.py").read


def ps(name, t0, t1, counters=None, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs,
            "counters": {k: {"count": 1, "bytes": 0, "seconds": v,
                             "max_s": v} for k, v in (counters or {}).items()}}


def bs(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1}


def cycle_run():
    """Two cycles: migrate [0, 10] and [20, 30], restore [11, 18] and
    [31, 39]; program spans inside them, and some outside any."""
    bench_spans = [bs("window", 0, 40), bs("cycle", 0, 19),
                   bs("migrate", 0, 10), bs("restore", 11, 18),
                   bs("cycle", 20, 40), bs("migrate", 20, 30),
                   bs("restore", 31, 39)]
    prog = [
        # cycle 1: the state's capture, and the dump's of the host copy
        ps("ckpt.capture", 0.5, 2.5), ps("ckpt.capture", 6.0, 6.25),
        ps("ckpt.tree_digest", 2.5, 5.5),
        ps("ckpt.dump", 5.9, 9.9, {"chunk_hash": 6.0, "fsync": 9.0}),
        ps("ckpt.dump.chunks", 6.5, 9.5, {"chunk_hash": 6.0}),
        ps("ckpt.restore.chunks", 11.0, 15.0),
        ps("ckpt.cast", 15.0, 16.0), ps("ckpt.tree_digest", 16.0, 17.5),
        # cycle 2
        ps("ckpt.capture", 20.5, 21.5),
        ps("ckpt.tree_digest", 21.5, 24.5),
        ps("ckpt.dump", 25.0, 29.0, {"chunk_hash": 4.0, "fsync": 3.0}),
        ps("ckpt.dump.chunks", 25.0, 28.0),
        ps("ckpt.restore.chunks", 31.0, 36.0),
        ps("ckpt.cast", 36.0, 36.5), ps("ckpt.tree_digest", 36.5, 38.5),
        # outside every migrate and restore: the post-window check
        ps("ckpt.capture", 40.5, 45.0), ps("ckpt.tree_digest", 10.0, 10.9),
        ps("ckpt.restore.chunks", 18.5, 19.5),
        # begins inside a migrate, ends after it: not inside
        ps("ckpt.dump.chunks", 9.0, 12.0),
    ]
    return {"spans": bench_spans, "program_spans": prog}


@pytest.mark.parametrize("name,want", [
    ("migrate.capture_s", ((2.0 + 0.25) + 1.0) / 2),
    ("migrate.digest_s", (3.0 + 3.0) / 2),
    ("migrate.chunks_s", (3.0 + 3.0) / 2),
    ("migrate.hash_s", (6.0 + 4.0) / 2),
    ("migrate.fsync_s", (9.0 + 3.0) / 2),
    ("restore.chunks_s", (4.0 + 5.0) / 2),
    ("restore.digest_s", (1.5 + 2.0) / 2),
    ("restore.cast_s", (1.0 + 0.5) / 2),
])
def test_cycle_readers_mean_per_cycle(name, want):
    assert reader(name)(cycle_run()) == pytest.approx(want)


def save_run():
    """Two async saves, save_call [0, 1] and [10, 11]; each submits a
    background dump that starts later on the lane."""
    bench_spans = [bs("window", 0, 20), bs("step", 1, 2),
                   bs("save_call", 0, 1), bs("save_call", 10, 11)]
    prog = [
        ps("ckpt.save_async", 0.05, 0.95),
        ps("ckpt.capture", 0.1, 0.8), ps("ckpt.capture", 0.8, 0.81),
        ps("ckpt.dump", 1.5, 6.5, queued_s=0.7),       # submitted at 0.8
        ps("ckpt.capture", 10.1, 10.6),
        ps("ckpt.dump", 11.2, 14.2, queued_s=0.5),     # submitted at 10.7
        # a dump no save call submitted: synchronous, or submitted later
        ps("ckpt.dump", 15.0, 16.0),
        ps("ckpt.dump", 16.0, 17.0, queued_s=0.5),
    ]
    return {"spans": bench_spans, "program_spans": prog}


def test_save_capture_mean_per_save():
    assert reader("save.capture_s")(save_run()) == pytest.approx(
        ((0.7 + 0.01) + 0.5) / 2)


def test_bg_dump_is_the_dump_each_save_submitted():
    assert reader("save.bg_dump_s")(save_run()) == pytest.approx(
        (5.0 + 3.0) / 2)


NAMES = ["migrate.capture_s", "migrate.digest_s", "migrate.chunks_s",
         "migrate.hash_s", "migrate.fsync_s", "restore.chunks_s",
         "restore.digest_s", "restore.cast_s", "save.capture_s",
         "save.bg_dump_s"]


@pytest.mark.parametrize("name", NAMES)
def test_none_without_program_spans(name):
    read = reader(name)
    for run in (cycle_run(), save_run()):
        assert read(dict(run, program_spans=None)) is None   # no module
        assert read(dict(run, program_spans=[])) is None
    # program spans, but none of this reader's inside its benchmark spans
    assert read({"spans": [bs("window", 0, 1)],
                 "program_spans": cycle_run()["program_spans"]}) is None


def test_every_new_metric_is_declared_with_its_reader():
    spec = bench.load_spec()
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NAMES:
        m = declared[name]
        assert (METRICS / f"{name}.py").exists()
        assert m["source"] == "host_clock" and m["unit"] == "s"
        assert len(m["workloads"]) == 1


def test_spans_come_from_the_program_when_not_given():
    from repro.core import tracing
    tracing.reset()
    with tracing.span("ckpt.capture"):
        pass
    (s,) = program_spans.program_spans({"spans": []})
    assert s["name"] == "ckpt.capture" and s["t1"] >= s["t0"]
    tracing.reset()
