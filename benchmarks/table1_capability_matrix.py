"""Paper Table 1 reproduction, driven by the `criu check` analogue.

The row list — which use cases exist, what CRIU achieved on each — lives in
ONE place: repro.api.capabilities (TABLE1 + the per-row Capability probes).
This benchmark iterates capabilities().table1_rows() and, for every row,
runs the heavy exercise registered for that capability name (dump ->
restore -> inspect with the strongest available oracle, bitwise
continuation where meaningful). A row is "Working" only if BOTH the cheap
environment probe and the full exercise pass; a Table-1 row without an
exercise here is a hard error, so the probe surface and the reproduction
matrix cannot drift apart."""
from __future__ import annotations

import os
import tempfile
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.api import CheckpointSession, RestoreRequest, capabilities
from repro.core import PreemptionHandler, restore, train_meta
from repro.core.storage import LocalDirTier
from repro.data import DataIterator, TokenDataset
from repro.models import LM
from repro.optim import OptConfig
from repro.serving import ServeEngine
from repro.training.train_loop import init_train_state, make_train_step


def _env():
    cfg = configs.get_tiny("qwen3-8b")
    lm = LM(cfg)
    step = jax.jit(make_train_step(lm, OptConfig(warmup_steps=2,
                                                 total_steps=100)))
    return cfg, lm, step


def _train(lm, step_fn, state, it, n):
    for _ in range(n):
        state, m = step_fn(state, {"tokens": jnp.asarray(it.next())})
    return state, m


def _bitwise(a, b):
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def serial_dump_restore(tmp):
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d1", vocab_size=cfg.vocab_size, seed=1)
    ref, _ = _train(lm, step, init_train_state(lm, jax.random.PRNGKey(0)),
                    DataIterator(ds, global_batch=2, seq_len=32), 6)
    st, _ = _train(lm, step, init_train_state(lm, jax.random.PRNGKey(0)),
                   DataIterator(ds, global_batch=2, seq_len=32), 4)
    sess = CheckpointSession(f"file://{tmp}/ck1")
    it = DataIterator(ds, global_batch=2, seq_len=32, step=4)
    sess.save(st, step=4, meta=train_meta(arch=cfg.name, step=4,
                                          data_state=it.state()))
    got, man = sess.load_latest(target_struct=jax.eval_shape(
        lambda: init_train_state(lm, jax.random.PRNGKey(0))))
    got = jax.tree.map(jnp.asarray, got)
    it2 = DataIterator.restore(ds, man["meta"]["data"])
    got, _ = _train(lm, step, got, it2, 2)
    assert _bitwise(ref, got)
    return "bitwise-identical continuation after dump/restore"


def threaded_dump(tmp):
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d2", vocab_size=cfg.vocab_size, seed=2)
    it = DataIterator(ds, global_batch=2, seq_len=32)
    it.start_prefetch()                      # live worker thread
    st = init_train_state(lm, jax.random.PRNGKey(0))
    for _ in range(3):
        st, _ = step(st, {"tokens": jnp.asarray(it.next_prefetched())})
    sess = CheckpointSession(f"file://{tmp}/ck2")
    sess.save_async(st, step=3, meta=train_meta(  # async writer thread
        arch=cfg.name, step=3, data_state=it.state()))
    sess.wait()
    it.stop_prefetch()                       # quiesce = state is step-only
    got, man = sess.load_latest(target_struct=jax.eval_shape(
        lambda: init_train_state(lm, jax.random.PRNGKey(0))))
    assert _bitwise(st, jax.tree.map(jnp.asarray, got))
    assert man["meta"]["data"]["step"] == 3
    return "dump with live prefetch+writer threads; quiesce at step boundary"


def open_file_cursors(tmp):
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d3", vocab_size=cfg.vocab_size, seed=3)
    it = DataIterator(ds, global_batch=2, seq_len=32)
    it.next(); it.next()
    state = it.state()
    # restore against the SAME corpus generated at a DIFFERENT path
    ds2 = TokenDataset(f"{tmp}/relocated/d3", vocab_size=cfg.vocab_size,
                       seed=3)
    it2 = DataIterator.restore(ds2, state)
    want = DataIterator(ds, global_batch=2, seq_len=32, step=2).next()
    assert np.array_equal(it2.next(), want)
    return "file cursors restored; path-independent (beyond CRIU's same-tree rule)"


def env_fingerprint_portability(tmp):
    cfg, lm, step = _env()
    st = init_train_state(lm, jax.random.PRNGKey(0))
    fake = {"jax": "0.0.0-containerA", "backend": "tpu", "device_count": 256,
            "python": "3.11.0", "machine": "aarch64"}
    with mock.patch("repro.core.manifest.env_fingerprint", return_value=fake):
        CheckpointSession(f"file://{tmp}/ck4").save(st, step=1)
    got, man = restore(f"{tmp}/ck4", allow_env_mismatch=True)
    assert man["env"] == fake
    assert _bitwise(st, jax.tree.map(jnp.asarray, got))
    return "image from a different env fingerprint restores cleanly (recorded, not required)"


def self_checkpoint(tmp):
    cfg, lm, step = _env()
    st = init_train_state(lm, jax.random.PRNGKey(0))
    with PreemptionHandler() as h:
        h.request()                       # runtime-internal trigger
        assert h.preempt_requested()
        sess = CheckpointSession(f"file://{tmp}/ck5")
        sess.save(st, step=1)             # the job dumps ITSELF
    got, _ = sess.load_latest()
    assert _bitwise(st, jax.tree.map(jnp.asarray, got))
    return "job checkpoints itself — no outside dumper agent (apptainer gap closed)"


def backend_retarget(tmp):
    cfg, lm, _ = _env()
    st = init_train_state(lm, jax.random.PRNGKey(0))
    sess = CheckpointSession(f"file://{tmp}/ck6")
    sess.save(st, step=1)
    got, man = sess.load_latest()
    got = jax.tree.map(jnp.asarray, got)
    # restore re-lowers for the current backend: fresh jit, fresh compile
    step2 = jax.jit(make_train_step(lm, OptConfig()))
    ds = TokenDataset(f"{tmp}/d6", vocab_size=cfg.vocab_size, seed=6)
    _, m = step2(got, {"tokens": jnp.asarray(
        DataIterator(ds, global_batch=2, seq_len=32).next())})
    assert jnp.isfinite(m["loss"])
    return "state is abstract; restore recompiles for the target backend"


def device_state_capture(tmp):
    cfg, lm, _ = _env()
    st = init_train_state(lm, jax.random.PRNGKey(0))
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(st))
    sess = CheckpointSession(f"file://{tmp}/ck7")
    sess.save(st, step=1)                  # device buffers ARE the state
    got, _ = sess.load_latest()
    got = jax.tree.map(jnp.asarray, got)   # device_put on restore
    assert _bitwise(st, got)
    return "device arrays captured via device_get; CRIU's hardest gap closed at framework level"


def serving_session_migration(tmp):
    cfg = configs.get_tiny("gemma2-2b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8),
                                            0, cfg.vocab_size))
    eng = ServeEngine(lm, params, max_len=32, donate_cache=False)
    eng.submit(prompts)
    ref = eng.generate(12)
    eng2 = ServeEngine(lm, params, max_len=32, donate_cache=False)
    eng2.submit(prompts)
    eng2.generate(5)
    sess = CheckpointSession(f"file://{tmp}/ck8")
    eng2.checkpoint(sess, arch=cfg.name)
    eng3 = ServeEngine(lm, params, max_len=32, donate_cache=False)
    eng3.resume_from(sess)
    assert np.array_equal(eng3.generate(12), ref)
    return "in-flight serving session migrated across engines, bitwise output"


def replica_repair(tmp):
    cfg, lm, _ = _env()
    st = init_train_state(lm, jax.random.PRNGKey(0))
    remote = LocalDirTier(f"{tmp}/remote_fs", write_latency_s=0.001)
    sess = CheckpointSession(f"file://{tmp}/ck9", replicas=(remote,))
    sess.save(st, step=1)
    # corrupt local, restore via replica repair
    import glob
    victim = glob.glob(f"{tmp}/ck9/chunks/*.bin")[0]
    open(victim, "wb").write(b"bitrot")
    got, _ = sess.load_latest()
    assert _bitwise(st, jax.tree.map(jnp.asarray, got))
    return "remote-FS replica tier + integrity verification + bitrot repair"


def cross_topology_restore(tmp):
    """Distributed (the MPI row): subprocess with 8 devices — dump sharded
    on mesh (4,2), restore on (2,4) and (8,1)."""
    import subprocess, sys, textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"    # 8 simulated host devices, never a chip
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src")),
         env.get("PYTHONPATH", "")])
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, tempfile
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.models.model import LM
        from repro.training.train_loop import init_train_state, train_state_pspecs
        from repro.launch.mesh import make_test_mesh
        from repro.api import CheckpointSession
        cfg = configs.get_tiny("qwen3-8b")
        lm = LM(cfg)
        tmp = tempfile.mkdtemp()
        mesh_a = make_test_mesh((4, 2), ("data", "model"))
        sps = lambda mesh: jax.tree.map(
            lambda ps: NamedSharding(mesh, ps),
            train_state_pspecs(lm, shd.make_rules(cfg, mesh)),
            is_leaf=lambda x: isinstance(x, P))
        st = init_train_state(lm, jax.random.PRNGKey(0))
        st_a = jax.tree.map(jax.device_put, st, sps(mesh_a))
        CheckpointSession(tmp).save(st_a, step=1)
        for shape in ((2, 4), (8, 1)):
            mesh_b = make_test_mesh(shape, ("data", "model"))
            got, _ = CheckpointSession(tmp).load_latest(
                target_struct=jax.eval_shape(
                    lambda: init_train_state(lm, jax.random.PRNGKey(0))),
                shardings=sps(mesh_b))
            for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
                assert bool(jnp.all(jnp.asarray(a) == jnp.asarray(b)))
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
    return "sharded job dumped under step barrier; elastic restore (4,2)->(2,4)->(8,1)"


def pre_dump(tmp):
    """Row 11: iterative pre-copy. A pre-dump round streams the full model
    while 'training continues' (here: one more partial update), and the
    final boundary dump re-emits every digest-unchanged leaf — the freeze
    window pays only for the residual dirty set. Restore must be bitwise
    the final state (and identical to a monolithic dump of it)."""
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d11", vocab_size=cfg.vocab_size, seed=11)
    st, _ = _train(lm, step, init_train_state(lm, jax.random.PRNGKey(0)),
                   DataIterator(ds, global_batch=2, seq_len=32), 2)
    sess = CheckpointSession(f"file://{tmp}/ck11")
    r = sess.pre_dump(st, step=2)
    assert r["stats"]["leaves_dirty"] > 0
    # a partial update: optimizer state drifts, params frozen — the
    # common "most leaves stable between rounds" regime
    st2 = jax.tree.map(jnp.asarray, st)
    st2["opt"] = jax.tree.map(lambda x: x + 0.125, st2["opt"])
    st2["step"] = st["step"] + 1
    out = sess.save(st2, step=3)
    assert out["stats"]["leaves_reused"] > 0, out["stats"]
    assert out["stats"]["bytes_stored"] < r["stats"]["bytes_stored"], \
        (out["stats"], r["stats"])
    got, _ = sess.load_latest(target_struct=jax.eval_shape(lambda: st2))
    assert _bitwise(st2, jax.tree.map(jnp.asarray, got))
    mono = CheckpointSession(f"file://{tmp}/ck11m")
    mono.save(st2, step=3)
    got2, _ = mono.load_latest(target_struct=jax.eval_shape(lambda: st2))
    assert _bitwise(jax.tree.map(jnp.asarray, got),
                    jax.tree.map(jnp.asarray, got2))
    return (f"residual dump reused {out['stats']['leaves_reused']} leaves, "
            f"stored {out['stats']['bytes_stored']}B vs "
            f"{r['stats']['bytes_stored']}B; restore bitwise == monolithic")


def lazy_restore(tmp):
    """Row 12: post-copy restore. Skeleton first, leaves on fault; fully
    materialized tree must equal the eager restore bit-for-bit."""
    cfg, lm, _ = _env()
    st = init_train_state(lm, jax.random.PRNGKey(0))
    sess = CheckpointSession(f"file://{tmp}/ck12")
    sess.save(st, step=1)
    eager, _ = sess.load_latest()
    res = sess.restore(RestoreRequest(lazy=True, prefetch_order=("params",)))
    srv = res.state.server
    total = len(srv.paths())
    first = res.state["params"]          # fault just the params subtree
    first.materialize()
    full = res.state.materialize()
    assert srv.remaining == 0
    assert _bitwise(jax.tree.map(jnp.asarray, eager),
                    jax.tree.map(jnp.asarray, full))
    return (f"skeleton of {total} leaves immediate; "
            f"{srv.stats['prefetched']} prefetched + "
            f"{srv.stats['faults']} faulted; materialized == eager bitwise")


def remote_storage(tmp):
    """Row 13: the migration image travels through a remote, slow, faulty
    object store. Dump on 'host A' through a write-through cache; restore
    on 'host B' — same store, empty cache (a new machine has no local
    state) — surviving injected transient faults via retries. The restored
    continuation must be bitwise identical, and a second host-B restore
    must be a pure cache hit (zero additional remote GETs)."""
    from repro.core.remote import (CachingTier, FaultPolicy, NetworkModel,
                                   RemoteTier, RetryPolicy,
                                   SimulatedObjectStore)
    from repro.core.storage import MemoryTier
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d13", vocab_size=cfg.vocab_size, seed=13)
    st, _ = _train(lm, step, init_train_state(lm, jax.random.PRNGKey(0)),
                   DataIterator(ds, global_batch=2, seq_len=32), 3)
    store = SimulatedObjectStore(
        network=NetworkModel(latency_s=0.0005),
        faults=FaultPolicy(seed=13, fail_rate=0.3, max_consecutive=2))
    remote = RemoteTier(store, retry=RetryPolicy(attempts=4),
                        part_bytes=64 << 10)
    host_a = CachingTier(MemoryTier(), remote)
    sess = CheckpointSession(host_a)
    it = DataIterator(ds, global_batch=2, seq_len=32, step=3)
    sess.save(st, step=3, meta=train_meta(arch=cfg.name, step=3,
                                          data_state=it.state()))
    host_b = CachingTier(MemoryTier(), remote)    # new resource, cold cache
    got, man = CheckpointSession(host_b).load_latest(
        target_struct=jax.eval_shape(
            lambda: init_train_state(lm, jax.random.PRNGKey(0))))
    assert _bitwise(st, jax.tree.map(jnp.asarray, got))
    gets = store.stats["gets"]
    got2, _ = CheckpointSession(host_b).load_latest()
    assert _bitwise(st, jax.tree.map(jnp.asarray, got2))
    assert store.stats["gets"] == gets, "warm restore hit the remote"
    return (f"image migrated via object store: "
            f"{remote.stats['parts_uploaded']} parts, "
            f"{remote.stats['retries']} faults retried, cold restore "
            f"bitwise, warm restore 100% cache")


def device_codec(tmp):
    """Row 14: the dump hot path runs on the device. Same model state
    dumped with the host codec and with the fused device encode+digest
    stage must restore bit-identically (and to each other); the device
    dump must actually route leaves through the stage, and decode must
    verify the fused payload digests."""
    from repro.api import CodecPolicy, SessionConfig
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d14", vocab_size=cfg.vocab_size, seed=14)
    st, _ = _train(lm, step, init_train_state(lm, jax.random.PRNGKey(0)),
                   DataIterator(ds, global_batch=2, seq_len=32), 2)
    st2, _ = _train(lm, step, st,
                    DataIterator(ds, global_batch=2, seq_len=32, step=2), 1)
    struct = jax.eval_shape(lambda: init_train_state(
        lm, jax.random.PRNGKey(0)))
    outs = {}
    for mode in ("off", "on"):
        sess = CheckpointSession(SessionConfig(
            root=f"file://{tmp}/ck14_{mode}",
            codec=CodecPolicy(optimizer="delta8", device=mode)))
        sess.save(st, step=2)
        out = sess.save(st2, step=3)       # delta8 vs the step-2 baseline
        if mode == "on":
            assert out["stats"]["leaves_device"] > 0, out["stats"]
            assert any("digest" in r["codec_meta"] for r in out["records"])
        got, _ = sess.load_latest(target_struct=struct)
        outs[mode] = jax.tree.map(jnp.asarray, got)
    assert _bitwise(outs["off"], outs["on"])
    n = sum(1 for _ in jax.tree.leaves(outs["on"]))
    return (f"device-encoded dump restores bitwise == host-codec dump "
            f"({n} leaves, fused payload digests verified on decode)")


def fleet_coordination(tmp):
    """Row 15: DMTCP's territory — a coordinator over many jobs. An
    8-job fleet on 4 hosts survives a full preemption wave with one
    seeded node failure striking mid-dump: drains, staggered dumps, the
    lost job re-placed from its last committed image, every restore
    bit-identical by recorded digest, and every coordinator<->job
    interaction a versioned wire frame."""
    from repro.fleet import SimCluster
    cl = SimCluster(hosts=4, devices_per_host=4, seed=15,
                    dump_concurrency=2, leaf_kb=8, leaves=3)
    cl.submit_jobs(8, steps=3)
    base = cl.coordinator.preemption_wave()
    assert len(base.dumped) == 8 and base.complete, base
    for j in cl.jobs:
        assert cl.coordinator.restore_job(j) is not None
    cl.tick(1.0, steps=2)
    picks = cl.seeded_failures(1, kind="MigrateRequest", span=8)
    assert len(picks) == 1
    cl.coordinator.preemption_wave()
    assert cl.coordinator.stats["hosts_failed"] == 1
    reg = cl.coordinator.registry
    alive = {h.host_id for h in cl.topology.hosts()}
    restored = 0
    for job_id in sorted(cl.jobs):
        rec = reg.get(job_id)
        if rec.phase != "dumped":
            continue                       # re-placed during the wave
        ack = cl.coordinator.restore_job(job_id)
        assert ack is not None and ack.host in alive
        assert ack.state_digest == rec.state_digest, job_id
        restored += 1
    for job_id in cl.jobs:
        assert reg.get(job_id).phase == "running", job_id
    frames = cl.coordinator.stats["wire_frames"]
    return (f"8-job wave + node failure seeded at dump frame "
            f"#{picks[0]}: lost jobs re-placed from committed images, "
            f"{restored} planned restores bit-identical, {frames} wire "
            f"frames")


def live_serving(tmp):
    """Row 16: a traffic-driven serving plane wave-migrated as a fleet
    job — drained at a DECODE boundary, dumped with its session table
    riding as meta, adopted by the next incarnation with every
    in-flight session intact and the serve clock preserved."""
    from repro.fleet import SimCluster
    cl = SimCluster(hosts=2, devices_per_host=2, seed=16,
                    dump_concurrency=1)
    (jid,) = cl.submit_serve_jobs(1, ticks=3)
    job = cl.jobs[jid]
    live = set(job.mgr.live_sids())
    clock = job.mgr.clock
    report = cl.coordinator.preemption_wave([jid])
    assert report.complete and jid in report.dumped, report
    rec = cl.coordinator.registry.get(jid)
    ack = cl.coordinator.restore_job(jid)
    assert ack is not None
    assert ack.state_digest == rec.state_digest
    assert live <= set(job.mgr.sessions), (live, set(job.mgr.sessions))
    assert job.mgr.clock == clock
    return (f"serve plane wave-migrated under traffic: {len(live)} "
            f"in-flight sessions survived the dump/adopt, clock {clock} "
            f"preserved, restore digest bit-identical")


def _socket_worker(tmp, server, job_id, seed):
    from repro.api.config import MigrationPolicy, SessionConfig
    from repro.fleet import FleetClient, ReconnectPolicy
    from repro.fleet.simcluster import SimJob
    job = SimJob(job_id, seed=seed, leaves=2, leaf_kb=4)
    job.run(3)
    cfg = SessionConfig(root=f"file://{tmp}/sock-{job_id}", serial=True,
                        migration=MigrationPolicy(arch="simjob"))

    def drain():
        job.paused = True
        return job.step

    client = FleetClient(job_id, cfg.to_wire(), host="w0",
                         state_provider=lambda: (job.state(), job.step),
                         on_drain=drain,
                         on_restore=lambda r: job.adopt(r.state, r.step))
    server.attach(job_id, cfg.to_wire(), host="w0")
    return client.connect(server.url, reconnect=ReconnectPolicy(
        attempts=120, backoff_s=0.02, backoff_max_s=0.2))


def socket_transport(tmp):
    """Row 17: the coordinator wire as REAL framed sockets. Two workers
    dial a UDS coordinator, a wave dumps both over the wire, the
    coordinator is killed (no bye, nothing flushed beyond the per-
    mutation journal) and restarted from the journaled registry — the
    workers re-bind at the bumped epoch and both restores complete
    bit-identical over the resumed connections."""
    from repro.fleet import coordinator_serve
    url = f"unix://{tmp}/t17-coord.sock"
    journal = f"file://{tmp}/t17-journal"
    server = coordinator_serve(url, registry_tier=journal,
                               resume_timeout_s=15.0)
    jobs = ["s0", "s1"]
    agents = [_socket_worker(tmp, server, j, 170 + i)
              for i, j in enumerate(jobs)]
    try:
        assert server.wait_connected(jobs, timeout=15.0)
        report = server.coordinator.preemption_wave(replace_lost=False)
        assert report.complete and len(report.dumped) == 2, report
        digests = {j: server.registry.get(j).state_digest for j in jobs}
        server.kill()                   # SIGKILL-shaped: no bye, no flush
        server2 = coordinator_serve(url, registry_tier=journal,
                                    resume_timeout_s=15.0)
        try:
            assert server2.epoch == 2
            assert server2.wait_connected(jobs, timeout=15.0)
            for j in jobs:
                ack = server2.coordinator.restore_job(j)
                assert ack is not None, j
                assert ack.state_digest == digests[j], j
            frames = server2.coordinator.stats["wire_frames"]
        finally:
            server2.close()
    finally:
        for a in agents:
            a.stop(bye=False)
    return (f"2 workers over a framed UDS: wave dumped both, coordinator "
            f"killed + restarted from the journaled registry (epoch 2), "
            f"workers re-bound and both restores bit-identical "
            f"({frames} wire frames after the restart)")


def cross_job_dedup(tmp):
    """Row 18: two jobs share one content-addressed pool. Job A trains
    and dumps; job B (same architecture, same state content) dumps into
    its OWN manifest namespace and must move zero chunk bytes — the
    global index answers every probe. Then job A is retained away and
    gc'd; job B must still restore bitwise identically (refcount journal
    protection), served from A's host's hot cache via peer fetch rather
    than the cold store."""
    from repro.core.registry import Registry
    from repro.core.remote import (CachingTier, NetworkModel, RemoteTier,
                                   RetryPolicy, SimulatedObjectStore)
    from repro.core.storage import MemoryTier
    cfg, lm, step = _env()
    ds = TokenDataset(f"{tmp}/d18", vocab_size=cfg.vocab_size, seed=18)
    st, _ = _train(lm, step, init_train_state(lm, jax.random.PRNGKey(0)),
                   DataIterator(ds, global_batch=2, seq_len=32), 3)
    store = SimulatedObjectStore(network=NetworkModel(latency_s=0.0005))
    alias = lambda p: RemoteTier(store, prefix=p, shared_chunks=True,
                                 retry=RetryPolicy(backoff_base_s=1e-4),
                                 part_bytes=64 << 10)
    job_a, job_b = alias("jobA"), alias("jobB")
    it = DataIterator(ds, global_batch=2, seq_len=32, step=3)
    host_a = CachingTier(MemoryTier(), job_a)
    CheckpointSession(host_a).save(
        st, step=3, meta=train_meta(arch=cfg.name, step=3,
                                    data_state=it.state()))
    bytes_a = store.stats["bytes_in"]
    res_b = CheckpointSession(job_b).save(
        st, step=3, meta=train_meta(arch=cfg.name, step=3,
                                    data_state=it.state()))
    assert job_b.stats["delta_chunks"] == 0, "shared pool re-uploaded"
    deduped = res_b["stats"]["chunks_deduped"]
    assert deduped > 0
    assert store.stats["bytes_in"] - bytes_a < bytes_a / 4
    reg_a = Registry(job_a)
    reg_a.truncate_from(0)
    gc = reg_a.gc()
    assert gc["removed"] == 0 and gc["kept"] > 0, "gc reaped shared chunks"
    host_b = CachingTier(MemoryTier(), job_b, peers=[host_a.hot])
    got, _ = CheckpointSession(host_b).load_latest(
        target_struct=jax.eval_shape(
            lambda: init_train_state(lm, jax.random.PRNGKey(0))))
    assert _bitwise(st, jax.tree.map(jnp.asarray, got))
    assert host_b.stats["peer_hits"] > 0, "peer fetch never engaged"
    return (f"job B deduped {deduped} chunks against job A's pool "
            f"(0 delta bytes), gc after A's retention kept "
            f"{gc['kept']} journal-referenced chunks, B restored "
            f"bitwise via {host_b.stats['peer_hits']} peer-cache hits")


# capability name -> heavy exercise; coverage of TABLE1 is asserted in run()
EXERCISES = {fn.__name__: fn for fn in (
    serial_dump_restore, threaded_dump, open_file_cursors,
    env_fingerprint_portability, self_checkpoint, backend_retarget,
    device_state_capture, serving_session_migration, replica_repair,
    cross_topology_restore, pre_dump, lazy_restore, remote_storage,
    device_codec, fleet_coordination, live_serving, socket_transport,
    cross_job_dedup)}


def run(emit=print) -> list:
    report = capabilities()
    rows = report.table1_rows()
    missing = [c.name for c in rows if c.name not in EXERCISES]
    assert not missing, f"Table-1 capabilities without an exercise: {missing}"
    results = []
    with tempfile.TemporaryDirectory() as tmp:
        for cap in rows:
            t0 = time.time()
            if not cap.supported:
                ours, evidence = "Not working", f"probe: {cap.detail}"
            else:
                try:
                    evidence = EXERCISES[cap.name](tmp)
                    ours = "Working"
                except Exception as e:  # pragma: no cover
                    evidence = f"FAILED: {e!r}"
                    ours = "Not working"
            dt = time.time() - t0
            results.append({"row": cap.paper_row, "test": cap.paper_name,
                            "capability": cap.name,
                            "paper_criu": cap.paper_verdict,
                            "repro": ours, "evidence": evidence,
                            "seconds": round(dt, 2)})
            emit(f"table1,row{cap.paper_row:02d}_{ours},{dt * 1e6:.0f},"
                 f"\"{cap.paper_name} | paper: {cap.paper_verdict} | "
                 f"ours: {ours}\"")
    return results


def markdown(results) -> str:
    lines = ["| # | Test (paper Table 1) | capability | CRIU (paper) | "
             "repro (this work) | evidence |",
             "|---|---|---|---|---|---|"]
    for r in results:
        lines.append(f"| {r['row']} | {r['test']} | `{r['capability']}` | "
                     f"{r['paper_criu']} | **{r['repro']}** | "
                     f"{r['evidence']} |")
    return "\n".join(lines)


if __name__ == "__main__":
    res = run()
    print()
    print(markdown(res))
