"""Preempt-to-migrate orchestration: the paper's central workflow as one
testable lifecycle.

The batch scheduler (OSPool/HTCondor) interrupts a running job at an
arbitrary point; the job must turn that interrupt into a restorable image
and a rescheduling request, and the *next* incarnation — possibly on a
different machine shape — must carry on as if nothing happened. The pieces
(signal handling, pipelined dump, elastic resharding, straggler policy)
exist as separate modules; this one composes them:

  dump side (MigrationOrchestrator):
    SIGTERM / SIGUSR2 / straggler escalation
      -> flag only (never dump mid-step; the step boundary is the quiesce
         point — device_get blocks on all in-flight collectives)
      -> at the boundary: quiesce the data pipeline, drain in-flight async
         dumps (their images are the incremental parents of this one),
         pipelined dump carrying a migration record (topology, DP degree,
         data cursor, RNG, logical-state digest, why), wait for
         durability, exit EXIT_CHECKPOINTED (85: "reschedule me anywhere")

  restore side (resume):
    latest image -> migration record -> plan_topology_change (N±k hosts,
    different DP degree; straggler dumps pre-plan the shrunken fleet)
      -> verify the restored logical state bit-identical via the integrity
         layer's topology-free tree digest
      -> reshard onto the new mesh, remap the data cursor

The contract tests (tests/test_migration.py) pin the strongest honest
invariant: with topology-invariant gradient aggregation
(training/elastic_dp.py), a run preempted mid-training and resumed on a
different host count reaches *bit-identical* state versus an unpreempted
run. Under XLA SPMD the restored image is still bit-exact, but the
continuation is only tolerance-equal across mesh shapes (reduction-order
rounding; see DESIGN.md §6)."""
from __future__ import annotations

import dataclasses
import logging
from typing import Any

import jax

from repro.core import tracing
from repro.core.dump import capture, flatten_with_paths
from repro.core.elastic import plan_topology_change, reshard
from repro.core.integrity import (CorruptionError, tree_digest,
                                  tree_digest_like)
from repro.core.preempt import EXIT_CHECKPOINTED, PreemptionHandler
from repro.core.state import train_meta

log = logging.getLogger(__name__)

MIGRATION_META_KEY = "migration"
MIGRATION_VERSION = 1


@dataclasses.dataclass(frozen=True)
class MigrationManifest:
    """What the next incarnation needs to carry on — stored (JSON) under
    meta["migration"] of the dump, next to but independent of the array
    manifest. Topology fields are a *record* of where the image came from,
    never a requirement on where it restores."""
    step: int
    arch: str = ""
    host_count: int = 1
    dp_degree: int = 1
    mesh_axes: list = dataclasses.field(default_factory=list)
    global_batch: int | None = None
    data: dict = dataclasses.field(default_factory=dict)   # iterator cursor
    rng: list | None = None            # e.g. raw PRNGKey words
    state_digest: str | None = None    # integrity.tree_digest of the dump
    reason: str | None = None          # SIGTERM / straggler / request / ...
    planned_host_count: int | None = None   # straggler escalation: restart
    planned_dp_degree: int | None = None    # ... already minus slow hosts
    hosts_dropped: list = dataclasses.field(default_factory=list)

    def to_meta(self) -> dict:
        d = dataclasses.asdict(self)
        d["version"] = MIGRATION_VERSION
        return d

    @classmethod
    def from_meta(cls, meta: dict) -> "MigrationManifest":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in meta.items() if k in fields})

    @classmethod
    def from_image(cls, manifest: dict) -> "MigrationManifest":
        """Read the record off an image manifest; synthesize a minimal one
        from train_meta/topology for pre-migration images (the lifecycle
        must be able to adopt any existing checkpoint)."""
        meta = manifest.get("meta", {})
        if MIGRATION_META_KEY in meta:
            return cls.from_meta(meta[MIGRATION_META_KEY])
        topo = manifest.get("topology", {})
        return cls(step=meta.get("step", manifest.get("step", 0)),
                   arch=meta.get("arch", ""),
                   host_count=topo.get("host_count", 1),
                   dp_degree=topo.get("dp_degree", 1),
                   mesh_axes=topo.get("axes", []),
                   global_batch=meta.get("data", {}).get("global_batch"),
                   data=meta.get("data", {}))


def _topology_of(mesh, topology: dict | None) -> dict:
    if topology is not None:
        return topology
    # lazy: core must stay importable without the distributed layer
    from repro.distributed.sharding import mesh_topology
    return mesh_topology(mesh)


class MigrationOrchestrator:
    """Composes PreemptionHandler + StragglerMonitor + Checkpointer into the
    dump side of the lifecycle. The training loop owns the step; the
    orchestrator owns everything between "something wants this job gone"
    and "the image is durable, exit 85"."""

    def __init__(self, checkpointer, *, handler: PreemptionHandler | None = None,
                 monitor=None, arch: str = "", mesh=None,
                 topology: dict | None = None, predump_rounds: int = 0):
        self.ckpt = checkpointer
        self.handler = handler or PreemptionHandler()
        self.monitor = monitor
        self.arch = arch
        self.mesh = mesh
        self.topology = topology
        self.predump_rounds = int(predump_rounds)
        self.predump_rounds_run = 0
        self.planned_host_count: int | None = None
        self.planned_dp_degree: int | None = None
        self.hosts_dropped: list = []
        self.last_migration: MigrationManifest | None = None
        self.last_image_id: str | None = None
        self.migrate_latency_s: float | None = None

    # ------------------------------------------------------------ lifecycle
    def install(self):
        self.handler.install()
        return self

    def uninstall(self):
        self.handler.uninstall()

    def __enter__(self):
        return self.install()

    def __exit__(self, *a):
        self.uninstall()

    # ------------------------------------------------------------- triggers
    def should_migrate(self) -> bool:
        """Poll at the step boundary — signals delivered mid-step only set
        the flag; the dump always happens here, never in the handler."""
        return self.handler.preempt_requested()

    def observe_step(self, host_times: list[float]) -> dict:
        """Feed per-host step times to the straggler policy and make its
        advice executable: ``checkpoint_and_replace`` escalates into a
        preemption request whose migration record pre-plans the shrunken
        fleet (restart defaults to N - dropped hosts)."""
        if self.monitor is None:
            return {"action": "none", "hosts": []}
        self.monitor.observe(host_times)
        advice = self.monitor.advice()
        if advice["action"] == "checkpoint_and_replace" \
                and not self.handler.preempt_requested():
            drop = list(advice["hosts"])
            keep = advice.get("suggested_host_count",
                              max(1, self.monitor.num_hosts - len(drop)))
            self.hosts_dropped = drop
            self.planned_host_count = keep
            # the restart DP degree scales with the surviving devices but
            # must preserve the dumped model-parallel factor: with
            # devices = dp * mp, dropping hosts shrinks dp, never mp. An
            # indivisible shape records no plan (resume() then keeps the
            # dumped dp or the caller chooses).
            topo = _topology_of(self.mesh, self.topology)
            dev = topo.get("device_count") or topo.get("host_count", 1)
            hostc = topo.get("host_count", 1) or 1
            dp = topo.get("dp_degree", 1) or 1
            mp = max(1, dev // dp)
            planned_devices = keep * max(1, dev // hostc)
            self.planned_dp_degree = planned_devices // mp \
                if planned_devices % mp == 0 else None
            self.handler.request("straggler")
            log.warning("straggler escalation: dropping hosts %s, planned "
                        "restart fleet %d", drop, keep)
        return advice

    def should_predump(self) -> bool:
        """True while a preemption is pending and configured pre-copy
        rounds remain: the loop should run pre_dump_round() and keep
        training toward its drain boundary instead of migrating yet. The
        rounds stream state out while steps still make progress, so the
        eventual migrate() freezes only for the residual dirty set."""
        return (self.handler.preempt_requested()
                and self.predump_rounds_run < self.predump_rounds)

    def pre_dump_round(self, state, *, step: int | None = None) -> dict:
        """One iterative pre-copy round between the preemption signal and
        the boundary drain (CRIU `criu pre-dump` before the final
        `criu dump`). Delegates to the checkpointer's pre_dump — a
        complete, restorable image whose cost is only the leaves dirtied
        since the previous round — and counts it against
        ``predump_rounds``."""
        if step is None:
            try:
                # the common dict-shaped train state: fetch ONE scalar,
                # not the whole tree (pre_dump captures the tree itself;
                # a second full device_get here would double the round's
                # host-transfer cost)
                step = int(jax.device_get(state["step"]))
            except (TypeError, KeyError, IndexError):
                pairs = dict(flatten_with_paths(jax.device_get(state)))
                step = int(pairs["step"]) if "step" in pairs else 0
        out = self.ckpt.pre_dump(
            state, step=step,
            topology=_topology_of(self.mesh, self.topology))
        self.predump_rounds_run += 1
        log.info("pre-dump round %d/%d: image %s (%d dirty / %d clean "
                 "leaves)", self.predump_rounds_run, self.predump_rounds,
                 out["image_id"], out["stats"]["leaves_dirty"],
                 out["stats"]["leaves_clean"])
        return out

    # ----------------------------------------------------------------- dump
    def build_manifest(self, *, step: int, data_state: dict | None,
                       state_digest: str | None, rng=None) -> MigrationManifest:
        topo = _topology_of(self.mesh, self.topology)
        data = data_state or {}
        gb = data.get("global_batch")
        planned_dp = self.planned_dp_degree
        if planned_dp and gb and gb % planned_dp:
            planned_dp = None   # indivisible plan would fail every default
            #                     restart; let resume fall back / choose
        return MigrationManifest(
            step=int(step), arch=self.arch,
            host_count=topo.get("host_count", 1),
            dp_degree=topo.get("dp_degree", 1),
            mesh_axes=topo.get("axes", []),
            global_batch=data.get("global_batch"),
            data=data,
            rng=[int(w) for w in jax.device_get(rng).ravel()]
            if rng is not None else None,
            state_digest=state_digest,
            reason=self.handler.reason,
            planned_host_count=self.planned_host_count,
            planned_dp_degree=planned_dp,
            hosts_dropped=self.hosts_dropped)

    def migrate(self, state, iterator=None, *, step: int | None = None,
                data_state: dict | None = None, rng=None,
                meta_extra: dict | None = None, opt_cfg=None) -> int:
        """The preempt path, start to durable: quiesce -> drain -> dump with
        migration record -> wait. Returns EXIT_CHECKPOINTED for the caller
        to sys.exit() with (the orchestrator never exits by itself — tests
        and multi-stage launchers need the control back). The whole body
        is a ``ckpt.migrate`` span; ``migrate_latency_s`` is its length."""
        with tracing.span("ckpt.migrate", rss=True) as sp:
            if iterator is not None and hasattr(iterator, "stop_prefetch"):
                iterator.stop_prefetch()
            # drain in-flight async dumps first: they are this image's
            # incremental ancestors and gc must never race their chunks
            self.ckpt.wait()
            if data_state is None and iterator is not None:
                data_state = iterator.state()
            host = capture(state)      # quiesce point: one capture shared
            pairs = flatten_with_paths(host)  # by digest and dump
            # the digest proves the restored bytes ARE the dumped bytes; a
            # lossy codec policy (delta8/bf16 optimizer state) breaks that
            # identity by design, so record no digest rather than make
            # every lossy migration image fail verification on resume
            digest = tree_digest(pairs) \
                if getattr(self.ckpt, "codec_policy", None) is None else None
            if step is None:
                step = int(dict(pairs)["step"])
            rec = self.build_manifest(step=step, data_state=data_state,
                                      state_digest=digest, rng=rng)
            meta = train_meta(arch=self.arch or "unknown", step=step,
                              data_state=data_state or {}, opt_cfg=opt_cfg,
                              extra=meta_extra)
            if meta_extra and "serve_plane" in meta_extra:
                # a serving plane migrated through the trainer path: the
                # image must announce itself so restorers rebuild sessions
                meta["job_kind"] = "serve"
                meta["serve_plane"] = meta_extra["serve_plane"]
                if "prefetch_hint" in meta_extra:
                    meta["prefetch_hint"] = meta_extra["prefetch_hint"]
            meta[MIGRATION_META_KEY] = rec.to_meta()
            out = self.ckpt.save(
                host, step=step, meta=meta,
                topology=_topology_of(self.mesh, self.topology))
            self.ckpt.wait()             # idempotent; async engines drain
        self.last_migration = rec
        self.last_image_id = out["image_id"]
        self.migrate_latency_s = sp.duration_s
        self.predump_rounds_run = 0   # a later migration pre-copies afresh
        log.info("migrated: image %s at step %d (%s) in %.3fs",
                 out["image_id"], step, rec.reason, self.migrate_latency_s)
        return EXIT_CHECKPOINTED


# -------------------------------------------------------------------- resume
@dataclasses.dataclass
class ResumeReport:
    state: Any
    manifest: dict
    migration: MigrationManifest
    topology_changed: bool
    changes: dict
    host_count: int
    dp_degree: int
    data: dict                    # remapped cursor (validate_elastic output)
    digest_verified: bool | None  # None: image predates digests

    def make_iterator(self, ds, *, dp_rank: int = 0, dp_size: int = 1,
                      prefetch: int = 2):
        """Remapped data cursor: same global batch -> the bitwise-identical
        global token stream; changed global batch -> the step was remapped
        by validate_elastic to the same token offset.

        dp_rank/dp_size are the DATA-FEEDING process layout — how many
        processes each feed a slice of the batch — NOT the mesh DP degree:
        a single-process SPMD job feeds the full global batch (the
        default), while a per-host pipeline passes its own rank and the
        feeding process count (typically host_count)."""
        from repro.data import DataIterator
        state = dict(self.migration.data)
        state["global_batch"] = self.data["global_batch"]
        state["step"] = self.data["step"]
        return DataIterator.restore(ds, state, dp_rank=dp_rank,
                                    dp_size=dp_size, prefetch=prefetch)


def resume(root, *, target_struct=None, shardings=None, mesh=None,
           host_count: int | None = None, dp_degree: int | None = None,
           global_batch: int | None = None, image_id: str | None = None,
           replicas=(), executor=None, verify_digest: bool = True,
           allow_env_mismatch: bool = True, lazy: bool = False,
           prefetch_order=None) -> ResumeReport:
    """Restore-side lifecycle: image -> migration record -> topology-change
    plan -> bit-identity verification -> reshard.

    The new topology comes from ``mesh`` (host/DP counts derived) or
    explicit ``host_count``/``dp_degree``; leaving both unset restarts on
    the dumped — or, after straggler escalation, the *planned* — fleet.
    Digest verification happens on the restored host tree BEFORE any
    device placement: what is being proven is that the bytes that came
    back are the bytes that were dumped, independent of where they are
    about to live.

    lazy: post-copy restore — the report's ``state`` is a LazyState whose
    skeleton is immediate and whose leaves fault in on access (prefetched
    in ``prefetch_order``; see core/lazy.py). Chunk hashes are still
    verified per read; the whole-tree digest check cannot run before the
    leaves exist, so ``digest_verified`` stays None in the report and the
    recorded digest is instead checked automatically the moment the tree
    fully materializes (state.materialize() — CorruptionError on
    mismatch, exactly like the eager path, just deferred);
    target_struct/shardings don't apply to a tree that isn't there yet —
    materialize() first, then cast/place. The whole body is a
    ``ckpt.restore`` span."""
    from repro.core.restore import restore as _restore

    with tracing.span("ckpt.restore", rss=True):
        if mesh is not None and (host_count is None or dp_degree is None):
            topo = _topology_of(mesh, None)
            host_count = host_count or topo["host_count"]
            dp_degree = dp_degree or topo["dp_degree"]

        if lazy:
            if target_struct is not None or shardings is not None:
                raise ValueError(
                    "lazy restore serves raw host leaves on fault; "
                    "target_struct/shardings apply after materialize() — "
                    "restore eagerly, or cast/device_put the materialized "
                    "tree yourself")
            from repro.core.lazy import lazy_restore
            tree, man, server = lazy_restore(
                root, image_id, replicas=replicas, executor=executor,
                prefetch_order=prefetch_order,
                allow_env_mismatch=allow_env_mismatch)
            if verify_digest:
                # deferred bit-identity: the server checks this digest when
                # the tree fully materializes (LazyState.materialize /
                # LeafServer.verify_tree_digest) — the lazy analogue of the
                # eager pre-placement check below
                server.expected_digest = \
                    MigrationManifest.from_image(man).state_digest
            pairs = None
        else:
            tree, man, pairs = _restore(root, image_id,
                                        target_struct=target_struct,
                                        replicas=replicas, executor=executor,
                                        allow_env_mismatch=allow_env_mismatch,
                                        with_pairs=True)
        rec = MigrationManifest.from_image(man)

        plan = plan_topology_change(
            {**dataclasses.asdict(rec), "data": rec.data},
            new_host_count=host_count, new_dp_size=dp_degree,
            global_batch=global_batch)

        digest_ok: bool | None = None
        if lazy:
            verify_digest = False     # nothing to digest until leaves arrive
        if verify_digest and rec.state_digest:
            # raw decoded bytes, pre-cast/place, in the recorded scheme
            got = tree_digest_like(pairs, rec.state_digest)
            digest_ok = got == rec.state_digest
            if not digest_ok:
                raise CorruptionError(man["image_id"],
                                      [f"state digest {got[:12]} != recorded "
                                       f"{rec.state_digest[:12]}"])
        if plan["changed"]:
            log.info("topology change on resume of %s: %s", man["image_id"],
                     plan["changes"])
        if shardings is not None:
            tree = reshard(tree, shardings)
        return ResumeReport(state=tree, manifest=man, migration=rec,
                            topology_changed=plan["changed"],
                            changes=plan["changes"],
                            host_count=plan["host_count"],
                            dp_degree=plan["dp_degree"], data=plan["data"],
                            digest_verified=digest_ok)
