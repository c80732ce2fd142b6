"""Elastic resize drill: dump a SHARDED training job on one topology and
continue it on another (the paper's unsolved 'parallel application' row).

Spawns a subprocess with 8 forced host devices and checks the invariants
that are actually true of cross-topology restore — each at its honest
strength:

  1. the image is topology-free: restoring the mesh-A dump onto mesh B
     (2,4) and mesh C (8,1) yields the BIT-IDENTICAL logical state (the
     migration layer proves it via the integrity tree digest);
  2. the continuation on mesh B is deterministic: restore + 4 steps, twice,
     agree bitwise (replay determinism — what a rescheduled job relies on);
  3. the continuation on mesh B matches the never-resharded 8-step run to
     numerical tolerance only — XLA re-associates reductions per shard
     size, so cross-mesh SPMD numerics differ at rounding level (~1e-4);
     DESIGN.md §6 explains why this is fundamental, not a bug;
  4. bit-identical cross-topology CONTINUATION is restored as a guarantee
     by the deterministic elastic-DP harness (per-example programs +
     global-order aggregation): a 4-host run preempted at step 4 and
     migrated to 2 hosts equals the unpreempted 4-host run, bitwise.

Run:  PYTHONPATH=src python examples/elastic_resize.py
"""
import os
import subprocess
import sys
import textwrap

ENV = dict(os.environ)
ENV["PYTHONPATH"] = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src"))
ENV["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
ENV["JAX_PLATFORMS"] = "cpu"    # 8 simulated host devices, never a chip

CODE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro import configs
    from repro.distributed import sharding as shd
    from repro.models.model import LM
    from repro.optim import OptConfig
    from repro.training.train_loop import (init_train_state, make_train_step,
                                           train_state_pspecs)
    from repro.launch.mesh import make_test_mesh
    from repro.api import (CheckpointSession, MigrateRequest,
                           MigrationPolicy, RestoreRequest, SessionConfig)
    from repro.data import DataIterator, TokenDataset

    cfg = configs.get_tiny("qwen3-8b")
    lm = LM(cfg)
    tmp = tempfile.mkdtemp()
    ds = TokenDataset(f"{tmp}/d", vocab_size=cfg.vocab_size, seed=0)
    opt = OptConfig(warmup_steps=2, total_steps=100)

    def stepper(mesh):
        rules = shd.make_rules(cfg, mesh)
        sps = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                           train_state_pspecs(lm, rules),
                           is_leaf=lambda x: isinstance(x, P))
        bsp = NamedSharding(mesh, P("data", None))
        fn = jax.jit(make_train_step(lm, opt), in_shardings=(sps, bsp),
                     out_shardings=(sps, None))
        return sps, bsp, fn

    def run(state, it, n, fn, bsp):
        for _ in range(n):
            toks = jax.device_put(jnp.asarray(it.next()), bsp)
            state, m = fn(state, {"tokens": toks})
        return state, m

    def leaves(t):
        return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(t))]

    def bitwise(a, b):
        return all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(b)))

    # ---- reference: 8 uninterrupted steps on mesh A (4 data, 2 model)
    mesh_a = make_test_mesh((4, 2), ("data", "model"))
    sps_a, bsp_a, fn_a = stepper(mesh_a)
    ref = jax.tree.map(jax.device_put, init_train_state(
        lm, jax.random.PRNGKey(0)), sps_a)
    it = DataIterator(ds, global_batch=8, seq_len=32)
    ref, _ = run(ref, it, 8, fn_a, bsp_a)

    # ---- elastic: 4 steps on A, dump via the migration lifecycle
    st = jax.tree.map(jax.device_put, init_train_state(
        lm, jax.random.PRNGKey(0)), sps_a)
    it1 = DataIterator(ds, global_batch=8, seq_len=32)
    st, _ = run(st, it1, 4, fn_a, bsp_a)
    sess = CheckpointSession(SessionConfig(
        root=f"file://{tmp}/ck",
        migration=MigrationPolicy(arch=cfg.name, mesh=mesh_a)))
    ticket = sess.migrate(MigrateRequest(state=st, iterator=it1,
                                         reason="resize-drill"))
    assert ticket.exit_code == 85
    print("dumped on mesh (4 data, 2 model) with migration record")

    # ---- invariant 1: restore onto B and C is bit-identical to the dump
    mesh_b = make_test_mesh((2, 4), ("data", "model"))
    sps_b, bsp_b, fn_b = stepper(mesh_b)
    struct = jax.eval_shape(lambda: init_train_state(
        lm, jax.random.PRNGKey(0)))
    rep = sess.restore(RestoreRequest(target_struct=struct,
                                      shardings=sps_b, mesh=mesh_b))
    assert rep.digest_verified, "integrity digest must prove bit-identity"
    assert rep.topology_changed and "dp_degree" in rep.changes, rep.changes
    assert bitwise(st, rep.state), "restored state != dumped state"
    print("restore onto (2 data, 4 model): bit-identical, digest verified")

    mesh_c = make_test_mesh((8, 1), ("data", "model"))
    sps_c, _, _ = stepper(mesh_c)
    rep_c = sess.restore(RestoreRequest(target_struct=struct,
                                        shardings=sps_c, mesh=mesh_c))
    assert rep_c.digest_verified and bitwise(st, rep_c.state)
    print("restore onto (8 data, 1 model): bit-identical — topology is a "
          "restore-time choice")

    # ---- invariant 2: replay determinism of the B continuation
    st_b = jax.tree.map(jnp.asarray, rep.state)
    it2 = rep.make_iterator(ds)
    st_b, _ = run(st_b, it2, 4, fn_b, bsp_b)
    rep2 = sess.restore(RestoreRequest(target_struct=struct,
                                       shardings=sps_b, mesh=mesh_b))
    st_b2, _ = run(jax.tree.map(jnp.asarray, rep2.state),
                   rep2.make_iterator(ds), 4, fn_b, bsp_b)
    assert bitwise(st_b, st_b2), "replayed continuation must be bitwise equal"
    print("continued on mesh (2 data, 4 model): replay-deterministic")

    # ---- invariant 3: B continuation == uninterrupted A run, to rounding
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(jax.device_get(ref))[0],
            jax.tree_util.tree_flatten_with_path(jax.device_get(st_b))[0]):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        np.testing.assert_allclose(
            a, b, rtol=1e-2, atol=1e-3,
            err_msg=f"cross-mesh continuation diverged beyond rounding at "
                    f"{jax.tree_util.keystr(pa)}")
    print("cross-mesh continuation equals uninterrupted run to rounding")

    # ---- invariant 4: deterministic elastic DP restores full bit-identity
    from repro.training.elastic_dp import ElasticDPTrainer
    ds2 = TokenDataset(f"{tmp}/d2", vocab_size=cfg.vocab_size, seed=1)
    ref_dp = ElasticDPTrainer(lm, opt, ds2, global_batch=8, seq_len=32,
                              hosts=4)
    ref_dp.run(6)
    t = ElasticDPTrainer(lm, opt, ds2, global_batch=8, seq_len=32, hosts=4)
    t.run(3)
    sess2 = CheckpointSession(SessionConfig(
        root=f"file://{tmp}/ck2",
        migration=MigrationPolicy(arch=cfg.name, topology=t.topology())))
    ticket2 = sess2.migrate(MigrateRequest(state=t.state,
                                           iterator=t.iters[0],
                                           reason="resize-drill"))
    assert ticket2.exit_code == 85
    rep_dp = sess2.restore(RestoreRequest(target_struct=struct,
                                          host_count=2, dp_degree=2))
    t2 = ElasticDPTrainer.from_resume(lm, opt, ds2, rep_dp, seq_len=32)
    t2.run(3)
    assert bitwise(ref_dp.state, t2.state), \\
        "deterministic elastic DP must be bit-identical across host counts"
    print("4-host -> 2-host migration, deterministic DP: bit-identical")
""")

out = subprocess.run([sys.executable, "-c", CODE], env=ENV, text=True)
assert out.returncode == 0
print("elastic resize drill OK")
