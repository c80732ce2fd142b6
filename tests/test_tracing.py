"""Spans and counters of the checkpoint layers (repro.core.tracing): how
they nest across the executor's pools, that a dump's counters are its own
even while another dump runs, that the counters agree with the receipts
they now feed, that the buffer is bounded, and that the spans reach a
profiler trace."""
import glob
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AsyncPolicy, CheckpointSession, CodecPolicy,
                       DumpRequest, MigrateRequest, MigrationPolicy,
                       RestoreRequest, SessionConfig)
from repro.core import tracing
from repro.core.executor import CheckpointExecutor
from repro.core.storage import LocalDirTier

CHUNK = 4096
TOPOLOGY = {"host_count": 1, "dp_degree": 1, "device_count": 1, "axes": []}


def state(seed=0):
    """Four leaves of 16-64 KiB of random f32: 4 KiB chunks never repeat."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"params": {"w": jax.random.normal(k[0], (64, 256)),
                       "b": jax.random.normal(k[1], (4096,))},
            "opt": {"m": jax.random.normal(k[2], (32, 128))},
            "step": jnp.asarray(seed, jnp.int32)}


def session(root, **kw):
    return CheckpointSession(SessionConfig(
        root=root, chunk_bytes=CHUNK,
        migration=MigrationPolicy(arch="t", topology=TOPOLOGY), **kw))


def named(name, spans=None):
    return [s for s in (tracing.spans() if spans is None else spans)
            if s["name"] == name]


def one(name):
    (s,) = named(name)
    return s


def duration(s):
    return s["t1"] - s["t0"]


@pytest.fixture(autouse=True)
def fresh():
    tracing.reset()
    yield
    tracing.reset()


def test_migrate_and_restore_nest_across_the_pools(tmp_path):
    sess = session(str(tmp_path / "ck"))
    with tracing.span("outer", rss=True) as outer:
        sess.migrate(MigrateRequest(state=state(1), step=3))
    mig = one("ckpt.migrate")
    assert mig["parent"] == outer.id
    save = one("ckpt.save")
    dump = one("ckpt.dump")
    chunks = one("ckpt.dump.chunks")
    assert save["parent"] == mig["id"] and dump["parent"] == save["id"]
    assert chunks["parent"] == dump["id"]
    by_parent = {s["parent"]: [] for s in tracing.spans()}
    for s in tracing.spans():
        by_parent[s["parent"]].append(s["name"])
    assert by_parent[mig["id"]] == ["ckpt.capture", "ckpt.tree_digest",
                                    "ckpt.save"]
    digest = one("ckpt.tree_digest")
    assert digest["attrs"]["blocks"] == 4
    assert digest["counters"]["digest_block"]["count"] == 4
    assert digest["counters"]["digest_block"]["bytes"] == \
        digest["attrs"]["bytes"]
    assert by_parent[dump["id"]] == ["ckpt.capture", "ckpt.dump.chunks"]
    assert "ckpt.gc" in by_parent[save["id"]]
    # children lie inside their parents on one clock
    for s in tracing.spans():
        p = [q for q in tracing.spans() if q["id"] == s["parent"]]
        if p:
            assert p[0]["t0"] <= s["t0"] <= s["t1"] <= p[0]["t1"]
    # work done on the ckpt-cpu and ckpt-io threads reached the spans
    # that submitted it, and every span above them
    n_chunks = sum(-(-a.nbytes // CHUNK) for a in jax.tree.leaves(state(1)))
    for s in (chunks, dump, save, mig):
        assert s["counters"]["chunk_hash"]["count"] == n_chunks
        assert s["counters"]["chunk_write"]["count"] == n_chunks
        assert s["counters"]["fsync"]["count"] >= n_chunks
    assert tracing.counters()["chunk_hash"]["count"] == n_chunks
    assert outer.attrs["rss_bytes"] > 0           # a root span
    assert "rss_bytes" not in mig["attrs"]        # not a root here

    tracing.reset()
    res = session(str(tmp_path / "ck")).restore(RestoreRequest(
        target_struct=jax.eval_shape(lambda: state(1))))
    assert res.digest_verified
    rst = one("ckpt.restore")
    assert rst["parent"] is None and rst["attrs"]["rss_bytes"] > 0
    rchunks, cast = one("ckpt.restore.chunks"), one("ckpt.cast")
    assert rchunks["parent"] == rst["id"] and cast["parent"] == rst["id"]
    assert one("ckpt.tree_digest")["parent"] == rst["id"]
    for name in ("chunk_read", "chunk_verify"):
        assert rchunks["counters"][name]["count"] == n_chunks
        assert rst["counters"][name]["count"] == n_chunks
    assert rchunks["counters"]["decode"]["count"] == 4
    nbytes = sum(a.nbytes for a in jax.tree.leaves(state(1)))
    assert cast["attrs"]["bytes"] == nbytes
    assert rchunks["counters"]["chunk_read"]["bytes"] == nbytes


@pytest.mark.parametrize("chunking", ["fixed", "cdc"])
def test_counters_match_the_receipts_without_dedup(tmp_path, chunking):
    sess = session(str(tmp_path / "ck"), codec=CodecPolicy(chunking=chunking))
    r = sess.dump(DumpRequest(state=state(2), step=1))
    d = one("ckpt.dump")["counters"]
    assert r.stats["bytes_stored"] > 0
    assert d["chunk_write"]["bytes"] == r.stats["bytes_stored"]
    assert d["chunk_hash"]["bytes"] == r.stats["bytes_raw"]
    assert d["chunk_hash"]["count"] == r.stats["chunks"]
    assert r.stats["chunks_deduped"] == 0
    # the same state again: every chunk is pooled, none is written
    r2 = sess.dump(DumpRequest(state=state(2), step=2))
    d2 = named("ckpt.dump")[-1]["counters"]
    assert r2.stats["bytes_stored"] == 0
    assert r2.stats["chunks_deduped"] == r2.stats["chunks"]
    assert "chunk_write" not in d2


def test_overlapping_async_dumps_keep_their_own_counters(tmp_path):
    """Two sessions on their own executors write at once, and one of them
    holds two dumps pending (max_pending=2). Each ckpt.dump counts only
    its own chunks."""
    slow = [LocalDirTier(str(tmp_path / n), write_latency_s=0.01)
            for n in ("a", "b")]
    a, b = (session(t, executor=CheckpointExecutor(),
                    async_dumps=AsyncPolicy(max_pending=2)) for t in slow)
    a.dump(DumpRequest(state=state(3), step=1, mode="async"))
    b.dump(DumpRequest(state=state(4), step=10, mode="async"))
    a.dump(DumpRequest(state=state(5), step=2, mode="async"))
    receipts = a.wait() + b.wait()
    spans = {s["attrs"]["step"]: s for s in named("ckpt.dump")}
    assert sorted(spans) == [1, 2, 10]
    a1, a2, b1 = spans[1], spans[2], spans[10]
    assert a1["t0"] < b1["t1"] and b1["t0"] < a1["t1"]      # overlapped
    # a's second dump was submitted while its first ran, and waited for it
    assert a1["t0"] < a2["t0"] - a2["attrs"]["queued_s"] < a1["t1"]
    assert a2["t0"] >= a1["t1"]
    for s in spans.values():
        assert s["parent"] is None
    for r in receipts:
        c = spans[r.step]["counters"]
        assert r.stats["bytes_stored"] > 0
        assert c["chunk_write"]["bytes"] == r.stats["bytes_stored"]
        assert c["chunk_hash"]["bytes"] == r.stats["bytes_raw"]


def test_receipt_durations_are_their_spans(tmp_path):
    sess = session(str(tmp_path / "ck"))
    r = sess.dump(DumpRequest(state=state(6), step=1))
    assert r.duration_s == duration(one("ckpt.save"))
    r = sess.dump(DumpRequest(state=state(7), step=2, mode="async"))
    assert r.duration_s == duration(one("ckpt.save_async"))
    sess.wait()
    r = sess.dump(DumpRequest(state=state(8), step=3, mode="pre_dump"))
    assert r.duration_s == duration(one("ckpt.pre_dump"))
    t = sess.migrate(MigrateRequest(state=state(9), step=4))
    assert t.latency_s == duration(one("ckpt.migrate"))
    # the root of an async save returns to the caller without reading RSS
    assert "rss_bytes" not in one("ckpt.save_async")["attrs"]
    assert all(s["attrs"]["rss_bytes"] > 0 for s in named("ckpt.dump")
               if s["parent"] is None)
    # the session's capture, then the async engine's of the host copy
    cap = [s for s in named("ckpt.capture")
           if s["parent"] == one("ckpt.save_async")["id"]]
    assert [s["attrs"]["bytes"] for s in cap] == 2 * [sum(
        a.nbytes for a in jax.tree.leaves(state(7)))]


def test_counters_reach_open_spans_only():
    with tracing.span("a") as a:
        with tracing.span("b") as b:
            tracing.count("x", 2, 10, 0.5)
        tracing.count("x", 1, 1, 0.25)
        stale = tracing._current.set(b)      # a task outliving its span
        tracing.count("x", 1, 1, 0.125)
        tracing._current.reset(stale)
    assert b.counter("x") == {"count": 2, "bytes": 10, "seconds": 0.5,
                              "max_s": 0.5}
    assert a.counter("x") == {"count": 4, "bytes": 12, "seconds": 0.875,
                              "max_s": 0.5}
    assert tracing.counters()["x"]["count"] == 4
    assert a.counter("never") == {"count": 0, "bytes": 0, "seconds": 0.0,
                                  "max_s": 0.0}


def test_counters_lose_no_update_under_contention():
    """More pool threads than cores, switching every microsecond, all
    counting into one span through tracing.submit."""
    workers, per = 4 * (os.cpu_count() or 4), 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(workers) as pool, tracing.span("s") as sp:
            def work():
                for _ in range(per):
                    tracing.count("n", 1, 2, 0.001)
            futs = [tracing.submit(pool, work) for _ in range(workers)]
            for f in futs:
                f.result(timeout=60)
    finally:
        sys.setswitchinterval(old)
    got = sp.counter("n")
    assert got["count"] == workers * per and got["bytes"] == 2 * workers * per
    assert got["seconds"] == pytest.approx(0.001 * workers * per)


def test_span_buffer_is_bounded():
    n = tracing.SPAN_BUFFER + 25
    for i in range(n):
        with tracing.span("tick", i=i):
            pass
    held = tracing.spans()
    assert len(held) == tracing.SPAN_BUFFER
    assert held[0]["attrs"]["i"] == 25 and held[-1]["attrs"]["i"] == n - 1


def test_profiler_trace_of_a_migrate_holds_the_spans(tmp_path):
    from jax.profiler import ProfileData
    sess = session(str(tmp_path / "ck"))
    tree = state(10)
    jax.block_until_ready(tree)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        sess.migrate(MigrateRequest(state=tree, step=1))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "trace", "**",
                                     "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"ckpt.migrate", "ckpt.capture", "ckpt.dump.chunks"} <= names


def test_stats_keys_are_unchanged(tmp_path):
    out = session(str(tmp_path / "ck")).save(state(11), step=1)
    assert set(out["stats"]) == {
        "bytes_raw", "bytes_stored", "bytes_deduped", "chunks",
        "chunks_deduped", "leaves_reused", "bytes_reused", "leaves_device",
        "chunks_reuploaded"}
    assert out["stats"]["bytes_raw"] == sum(
        np.asarray(a).nbytes for a in jax.tree.leaves(state(11)))
