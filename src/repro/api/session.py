"""CheckpointSession: the one door to the checkpoint/restore engine.

A session is opened from a typed SessionConfig, owns the storage tiers, the
registry, the (shared) plan/execute engine and the preemption/migration
machinery, and exposes the libcriu-style typed operations:

    with CheckpointSession(SessionConfig(root="file:///ckpts")) as sess:
        receipt = sess.dump(DumpRequest(state=state, step=s, meta=meta))
        ...
        if sess.should_migrate():
            ticket = sess.migrate(MigrateRequest(state=state, iterator=it))
            sys.exit(ticket.exit_code)

    # next incarnation, any machine / topology:
    res = CheckpointSession(cfg).restore(RestoreRequest(
        target_struct=struct, host_count=2, dp_degree=2))

The legacy facades (core.Checkpointer / core.AsyncCheckpointer) are thin
deprecation shims over a session — same engine, one implementation.

Implementation note: the session keeps untyped save/save_async/wait-raw
methods (`save`, `save_async`, `load`, `load_latest`) with the historical
dict-based signatures; the shims and the MigrationOrchestrator call these,
the typed request methods wrap them. One tier object is shared between the
dumper and its registry: gc must update the same in-memory chunk index the
dump path dedups against."""
from __future__ import annotations

import functools

from repro.api.config import SessionConfig
from repro.api.requests import (DumpReceipt, DumpRequest, MigrateRequest,
                                MigrationTicket, RestoreRequest,
                                RestoreResult)
from repro.core import tracing
from repro.core.async_engine import AsyncCheckpointer as _AsyncEngine
from repro.core.dump import capture
from repro.core.dump import dump as _dump
from repro.core.dump import flatten_with_paths, host_tree_by_path
from repro.core.executor import CheckpointExecutor, get_default_executor
from repro.core.plan import DumpPlan, plan_dump
from repro.core.registry import Registry
from repro.core.restore import restore as _restore
from repro.core.storage import as_tier


def _phase(name: str):
    """Run an engine method as the span ``name`` (core/tracing.py); the
    span stays on ``self._span``, whose length the typed receipts report."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(self, tree, *, step, **kw):
            with tracing.span(name, step=int(step)) as self._span:
                return fn(self, tree, step=step, **kw)
        return run
    return wrap


def _step_of(image_id: str) -> int | None:
    try:
        return int(image_id.rsplit("_", 1)[-1])
    except (ValueError, AttributeError):
        return None


class CheckpointSession:
    """Typed facade over the plan/execute engine (see module docstring).

    Example::

        with CheckpointSession(SessionConfig(root="file:///ckpts")) as s:
            s.dump(DumpRequest(state=state, step=1))
            if s.should_predump():
                s.pre_dump_round(state)          # pre-copy, keep training
            elif s.should_migrate():
                sys.exit(s.migrate(MigrateRequest(state=state)).exit_code)
        res = CheckpointSession("file:///ckpts").restore(
            RestoreRequest(lazy=True))           # post-copy resume
    """

    def __init__(self, config: SessionConfig | str, **overrides):
        """``config`` is a SessionConfig, or a root tier reference (URI,
        path or Tier) for the all-defaults session; ``overrides`` are
        SessionConfig field replacements for the shorthand form."""
        if not isinstance(config, SessionConfig):
            config = SessionConfig(root=config, **overrides)
        elif overrides:
            config = SessionConfig(**{
                **{f.name: getattr(config, f.name)
                   for f in config.__dataclass_fields__.values()},
                **overrides})
        self.config = config
        self.tier = as_tier(config.root)
        self.replicas = [as_tier(r) for r in config.replicas]
        self.codec_policy = config.codec.to_leaf_policy()
        self.incremental = config.codec.incremental
        self.chunk_bytes = config.chunk_bytes
        self.keep_last = config.retention.keep_last
        self.keep_every = config.retention.keep_every
        self.executor = config.executor or (
            CheckpointExecutor(serial=True) if config.serial
            else get_default_executor())
        self.registry = Registry(self.tier)
        self._async = None
        self._drained = []      # async results consumed by sync-save drains
        self._prev_host = None  # for delta8 chains
        self._prev_step = None  # step whose image _prev_host belongs to
        self._prev_image = None  # image id _prev_host is the content of
        self._tracker = None    # lazy DirtyLeafTracker (pre-dump rounds)
        self._orch = None       # lazy MigrationOrchestrator
        self._span = None       # the newest engine call's span
        self._installed = False
        self._closed = False

    # ------------------------------------------------------------ lifecycle
    def _orchestrator(self):
        if self._orch is None:
            from repro.core.migration import MigrationOrchestrator
            from repro.core.preempt import PreemptionHandler
            mig = self.config.migration
            self._orch = MigrationOrchestrator(
                self,
                handler=PreemptionHandler(
                    signals=self.config.preemption.signals),
                monitor=mig.monitor, arch=mig.arch, mesh=mig.mesh,
                topology=mig.topology,
                predump_rounds=mig.predump_rounds)
        return self._orch

    @property
    def handler(self):
        """The session's PreemptionHandler (flag-only signal recorder)."""
        return self._orchestrator().handler

    def __enter__(self):
        if self.config.preemption.install_signals:
            self._orchestrator().install()
            self._installed = True
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close(drain=exc_type is None)

    def close(self, drain: bool = True):
        """Drain in-flight async dumps (unless ``drain=False`` — e.g. the
        body raised and durability is moot) and release signal handlers."""
        if self._closed:
            return
        self._closed = True
        try:
            if drain and self._async is not None:
                self._wait_raw()
        finally:
            if self._installed:
                self._orch.uninstall()
                self._installed = False

    # ------------------------------------------------------- typed requests
    def dump(self, request: DumpRequest) -> DumpReceipt:
        """DumpRequest -> DumpReceipt. mode="async" returns an uncommitted
        receipt; the committed ones come back from wait(). mode="pre_dump"
        runs one iterative pre-copy round (see pre_dump()) and returns a
        committed receipt whose stats carry the dirty/clean split.

        Example::

            sess.dump(DumpRequest(state=state, step=s, mode="pre_dump"))
            ...                       # training continues, state drifts
            sess.dump(DumpRequest(state=state, step=s2))   # residual only
        """
        if not isinstance(request, DumpRequest):
            raise TypeError(f"dump() takes a DumpRequest, got "
                            f"{type(request).__name__} — build one, or use "
                            f"the legacy save() shim")
        if request.mode == "pre_dump":
            out = self.pre_dump(request.state, step=request.step,
                                meta=request.meta,
                                topology=request.topology)
            return DumpReceipt(step=int(request.step), mode="pre_dump",
                               committed=True, image_id=out["image_id"],
                               stats=out["stats"],
                               duration_s=self._span.duration_s)
        if request.mode == "async":
            if not self.config.async_dumps.enabled:
                raise RuntimeError("async dumps are disabled by this "
                                   "session's AsyncPolicy")
            self.save_async(request.state, step=request.step,
                            meta=request.meta, topology=request.topology)
            return DumpReceipt(step=int(request.step), mode="async",
                               committed=False,
                               duration_s=self._span.duration_s)
        out = self.save(request.state, step=request.step, meta=request.meta,
                        topology=request.topology)
        return DumpReceipt(step=int(request.step), mode="sync",
                           committed=True, image_id=out["image_id"],
                           stats=out["stats"],
                           duration_s=self._span.duration_s)

    def wait(self) -> list:
        """Barrier: every async dump enqueued since the last barrier is
        durable (or this raises). Returns their committed DumpReceipts."""
        return [DumpReceipt(step=_step_of(o["image_id"]), mode="async",
                            committed=True, image_id=o["image_id"],
                            stats=o["stats"])
                for o in self._wait_raw()]

    def restore(self, request: RestoreRequest | None = None) -> RestoreResult:
        """RestoreRequest -> RestoreResult: image -> migration record ->
        topology plan -> digest verification -> reshard. Defaults restore
        the latest image onto the dumped (or straggler-planned) fleet."""
        from repro.core.migration import resume
        req = request or RestoreRequest()
        if not isinstance(req, RestoreRequest):
            raise TypeError(f"restore() takes a RestoreRequest, got "
                            f"{type(req).__name__}")
        rep = resume(self.tier, target_struct=req.target_struct,
                     shardings=req.shardings, mesh=req.mesh,
                     host_count=req.host_count, dp_degree=req.dp_degree,
                     global_batch=req.global_batch, image_id=req.image_id,
                     replicas=self.replicas, executor=self.executor,
                     verify_digest=(req.verify_digest
                                    and self.config.migration.verify_digest),
                     allow_env_mismatch=req.allow_env_mismatch,
                     lazy=req.lazy, prefetch_order=req.prefetch_order)
        return RestoreResult(
            state=rep.state, image_id=rep.manifest["image_id"],
            step=int(rep.migration.step), manifest=rep.manifest,
            migration=rep.migration, topology_changed=rep.topology_changed,
            changes=rep.changes, host_count=rep.host_count,
            dp_degree=rep.dp_degree, data=rep.data,
            digest_verified=rep.digest_verified, report=rep,
            lazy=req.lazy)

    def migrate(self, request: MigrateRequest) -> MigrationTicket:
        """MigrateRequest -> MigrationTicket: quiesce -> drain -> dump with
        migration record -> durable. The caller owns the actual
        sys.exit(ticket.exit_code)."""
        if not isinstance(request, MigrateRequest):
            raise TypeError(f"migrate() takes a MigrateRequest, got "
                            f"{type(request).__name__}")
        orch = self._orchestrator()
        if not orch.handler.preempt_requested():
            orch.handler.request(request.reason or "request")
        code = orch.migrate(request.state, request.iterator,
                            step=request.step,
                            data_state=request.data_state, rng=request.rng,
                            meta_extra=request.meta_extra,
                            opt_cfg=request.opt_cfg)
        del code  # orchestrator returns EXIT_CHECKPOINTED; policy may remap
        rec = orch.last_migration
        return MigrationTicket(
            exit_code=self.config.preemption.exit_code,
            image_id=orch.last_image_id, step=rec.step, reason=rec.reason,
            latency_s=orch.migrate_latency_s, record=rec)

    # -------------------------------------------------- preemption / fleet
    def should_migrate(self) -> bool:
        """Poll at the step boundary: did a signal / escalation ask this
        job to go away? (The dump itself always happens here, never in the
        signal handler.)"""
        return self._orchestrator().should_migrate()

    def should_predump(self) -> bool:
        """True while a preemption is pending and MigrationPolicy's
        pre-copy budget (``predump_rounds``) has rounds left: run
        pre_dump_round() and keep training instead of migrating yet.

        Example::

            if sess.should_predump():
                sess.pre_dump_round(state)       # stream, keep stepping
            elif sess.should_migrate():
                ticket = sess.migrate(MigrateRequest(state=state))
                sys.exit(ticket.exit_code)       # residual freeze only
        """
        return self._orchestrator().should_predump()

    def pre_dump_round(self, state, *, step: int | None = None) -> dict:
        """One orchestrated pre-copy round on the way to migration
        (counts against MigrationPolicy.predump_rounds; the bare engine
        entry point is pre_dump())."""
        return self._orchestrator().pre_dump_round(state, step=step)

    def observe_step(self, host_times) -> dict:
        """Feed per-host step times to the straggler policy (configured via
        MigrationPolicy.monitor); persistent stragglers escalate into a
        preemption request whose record pre-plans the shrunken fleet."""
        return self._orchestrator().observe_step(host_times)

    def capabilities(self):
        """`criu check` for this session's environment + configuration."""
        from repro.api.capabilities import capabilities
        return capabilities(self.config)

    # --------------------------------------------------------- engine: save
    # Untyped engine methods. The typed requests above and the deprecation
    # shims in repro.core both route through these — one implementation.
    def _save_kw(self, step, meta, topology, with_parent: bool = True):
        parent = None
        prev_host = self._prev_host
        if not self.incremental:
            # no parent link will ever be written, so a delta8 leaf could
            # never be decoded — force full encodes
            prev_host = None
        elif with_parent:
            parent, prev_host = self.registry.resolve_parent_baseline(
                self._prev_step, prev_host, step,
                baseline_image=self._prev_image)
        kw = dict(step=step, meta=meta or {}, parent=parent,
                  codec_policy=self.codec_policy,
                  prev_host_tree=prev_host, topology=topology or {},
                  chunking_mode=self.config.codec.chunking,
                  device_codec=self.config.codec.device)
        if self.chunk_bytes:
            kw["chunk_bytes"] = self.chunk_bytes
        return kw

    def _classify(self, host):
        """(reuse_records, digests) from the dirty tracker — ({}, None)
        when no pre-dump round has warmed it, so sessions that never
        pre-dump pay nothing for the machinery."""
        if self._tracker is None or not self._tracker.warm:
            return {}, None
        from repro.core.predump import digest_pairs
        digests = digest_pairs(flatten_with_paths(host),
                               executor=self.executor)
        return self._tracker.reuse_for(digests), digests

    def _retain(self):
        """Retention policy, then pool gc, as a ``ckpt.gc`` span."""
        with tracing.span("ckpt.gc"):
            self.registry.retain(self.keep_last, self.keep_every)
            self.registry.gc()

    @_phase("ckpt.save")
    def save(self, tree, *, step: int, meta: dict | None = None,
             topology: dict | None = None) -> dict:
        """Raw-dict sync dump (the engine under DumpRequest(mode="sync")):
        blocks until the image is durable, returns {"image_id", "stats",
        "records"}. After pre-dump rounds this is automatically the
        residual dump — digest-unchanged leaves re-emit cached records.

        Example::

            out = sess.save(state, step=7)
            print(out["image_id"], out["stats"]["bytes_stored"])
        """
        if self._async is not None:
            # drain in-flight async dumps first: the submit-time parent
            # scan must see them committed (causal chain), and retain/gc
            # below must never run while a dump is still writing — gc
            # would reap its not-yet-manifest-referenced chunks. Keep the
            # drained results: the next wait() still owes them to the
            # caller
            self._drained.extend(self._async.wait())
        host = capture(tree)   # one capture, shared with the baseline
        # residual-dump path: after pre-dump rounds, digest-unchanged
        # leaves re-emit their cached records — the freeze window pays
        # only for the dirty set (plus the classification pass itself)
        reuse, digests = self._classify(host)
        out = _dump(host, self.tier, replicas=self.replicas,
                    executor=self.executor, reuse_records=reuse,
                    device_source=tree,   # device-resident when caller's is
                    **self._save_kw(step, meta, topology))
        if self.codec_policy is not None and self.incremental:
            self._prev_host = host_tree_by_path(host)
            self._prev_step = step
            self._prev_image = out["image_id"]
        if digests is not None:
            self._tracker.update(digests, out["records"], out["image_id"],
                                 pre_dump=False)
        self._retain()
        return out

    @_phase("ckpt.pre_dump")
    def pre_dump(self, tree, *, step: int, meta: dict | None = None,
                 topology: dict | None = None) -> dict:
        """One iterative pre-copy round (CRIU `criu pre-dump`): commit a
        complete, restorable image of the current state while training
        goes on, writing only leaves dirtied since the previous round.
        The dirty tracker remembers this round's records, so the *final*
        dump at the step boundary (an ordinary save()/DumpRequest) writes
        only the residual dirty set — that is the stop-the-world window
        this call exists to shrink.

        Returns {"image_id", "stats", "records"}; stats carry
        ``leaves_dirty``/``leaves_clean``/``predump_round``. Rounds never
        delta8-encode (a reused record must decode parent-free — see
        core/predump.py), but they do advance the session's delta8
        baseline so the final dump's dirty leaves delta against the last
        round's image."""
        from repro.core.predump import (PRE_DUMP_META_KEY, DirtyLeafTracker,
                                        digest_pairs)
        if self._tracker is None:
            self._tracker = DirtyLeafTracker()
        if self._async is not None:
            self._drained.extend(self._async.wait())   # causal parents
        host = capture(tree)
        pairs = flatten_with_paths(host)
        digests = digest_pairs(pairs, executor=self.executor)
        reuse = self._tracker.reuse_for(digests)
        latest = self.registry.latest()
        parent = latest["image_id"] if latest else None
        rnd = self._tracker.rounds
        existing = set(self.tier.image_ids())
        image_id = f"step_{int(step):010d}p{rnd:02d}"
        while image_id in existing:   # a foreign session's round at this
            rnd += 1                  # step: never overwrite an image a
            image_id = f"step_{int(step):010d}p{rnd:02d}"   # delta child
            #                           may decode through
        kw = dict(step=step, parent=parent, topology=topology or {},
                  codec_policy=self.codec_policy, prev_host_tree=None,
                  chunking_mode=self.config.codec.chunking,
                  device_codec=self.config.codec.device,
                  meta={**(meta or {}),
                        PRE_DUMP_META_KEY: {
                            "round": rnd,
                            "dirty": len(pairs) - len(reuse),
                            "clean": len(reuse)}})
        if self.chunk_bytes:
            kw["chunk_bytes"] = self.chunk_bytes
        out = _dump(host, self.tier, replicas=self.replicas,
                    executor=self.executor, image_id=image_id,
                    reuse_records=reuse, device_source=tree, **kw)
        self._tracker.update(digests, out["records"], out["image_id"],
                             pre_dump=True)
        if self.codec_policy is not None and self.incremental:
            self._prev_host = host_tree_by_path(host)
            self._prev_step = step
            self._prev_image = out["image_id"]
        self._retain()
        out["stats"]["predump_round"] = rnd
        out["stats"]["leaves_dirty"] = len(pairs) - len(reuse)
        out["stats"]["leaves_clean"] = len(reuse)
        return out

    @_phase("ckpt.save_async")
    def save_async(self, tree, *, step: int, meta: dict | None = None,
                   topology: dict | None = None):
        """Raw-dict async dump (the engine under DumpRequest(mode=
        "async")): captures device state now, writes in the background on
        the ordered lane; wait() is the durability barrier.

        Example::

            sess.save_async(state, step=7)   # returns at capture
            ...                              # training continues
            sess.wait()                      # durable (or raises)
        """
        if self._async is None:
            self._async = _AsyncEngine(
                self.tier, replicas=self.replicas,
                max_pending=self.config.async_dumps.max_pending,
                executor=self.executor)
        # parent=None here: the incremental link is resolved when the
        # ordered job runs (a submit-time registry scan would both block
        # the step and miss still-in-flight parents)
        kw = self._save_kw(step, meta, topology, with_parent=False)
        baseline_step = self._prev_step
        baseline_image = self._prev_image
        host = capture(tree)   # one capture: the job's input and
        #                        the next call's delta baseline
        if self.codec_policy is not None and self.incremental:
            # mirror save(): job N's delta baseline (kw's prev_host_tree,
            # the tree of the PRECEDING save call) must equal the content
            # of the image the job resolves as parent at run time, so the
            # next call's baseline becomes this tree
            self._prev_host = host_tree_by_path(host)
            self._prev_step = step
            self._prev_image = f"step_{int(step):010d}"  # dump()'s default
        self._async.dump_async(host, resolve_parent=self.incremental,
                               baseline_step=baseline_step,
                               baseline_image=baseline_image, **kw)

    def _wait_raw(self) -> list:
        if self._async is not None:
            out, self._drained = self._drained + self._async.wait(), []
            self._retain()
            return out
        return []

    # --------------------------------------------------------- engine: plan
    def plan(self, tree_or_abstract, *, step: int = 0) -> DumpPlan:
        """Dry-run dump plan (works on ShapeDtypeStructs — no device/tier
        access): leaf partition, codec decisions, sizes."""
        from repro.core.chunking import CHUNK_BYTES
        return plan_dump(flatten_with_paths(tree_or_abstract), step=step,
                         codec_policy=self.codec_policy,
                         prev_host_tree=self._prev_host,
                         chunk_bytes=self.chunk_bytes or CHUNK_BYTES)

    # --------------------------------------------------------- engine: load
    def load_latest(self, target_struct=None, shardings=None):
        """Raw restore of the newest image -> (tree, manifest). The typed
        path (RestoreRequest) adds migration/topology handling on top.

        Example::

            tree, man = sess.load_latest(target_struct=struct)
        """
        return _restore(self.tier, target_struct=target_struct,
                        shardings=shardings, replicas=self.replicas,
                        executor=self.executor)

    def load(self, image_id: str, target_struct=None, shardings=None):
        """Raw restore of a specific image id -> (tree, manifest).

        Example::

            tree, man = sess.load("step_0000000040")
        """
        return _restore(self.tier, image_id, target_struct=target_struct,
                        shardings=shardings, replicas=self.replicas,
                        executor=self.executor)
