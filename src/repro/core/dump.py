"""criu-dump for JAX job state: plan, then execute.

Flow: quiesce (device_get blocks on all in-flight work — no collective is
ever captured mid-flight, the step boundary IS the quiesce point) ->
plan_dump (leaf partition, codec applicability, chunk geometry — pure
data) -> CheckpointExecutor pipelines encode+hash and deduplicated pool
writes (unchanged chunks cost nothing — incremental dumps for free) ->
manifest committed last (atomic rename). Multi-host: leaves are partitioned
round-robin by process; each process writes a manifest part and process 0
merges (single-process containers just take the fast path)."""
from __future__ import annotations

import json

import jax
import numpy as np

from repro.core import chunking, manifest, tracing
from repro.core import device_codec as device_codec_mod
from repro.core.executor import CheckpointExecutor, get_default_executor
from repro.core.plan import plan_dump
from repro.core.storage import Tier, as_tier


def leaf_paths_of(tree) -> list:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]


def flatten_with_paths(tree) -> list:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
        out.append((p, leaf))
    return out


def capture(tree):
    """Device -> host copy of a state, the quiesce point (``device_get``
    blocks on all in-flight work), recorded as a ``ckpt.capture`` span."""
    with tracing.span("ckpt.capture") as sp:
        host = jax.device_get(tree)
        sp.set(bytes=sum(getattr(a, "nbytes", 0)
                         for a in jax.tree_util.tree_leaves(host)))
    return host


def dump(tree, root, *, step: int, image_id: str | None = None,
         meta: dict | None = None, parent: str | None = None,
         codec_policy=None, prev_host_tree: dict | None = None,
         replicas=(), topology: dict | None = None,
         chunk_bytes: int = chunking.CHUNK_BYTES, chunking_mode: str = "fixed",
         process_index: int = 0, num_processes: int = 1,
         executor: CheckpointExecutor | None = None,
         reuse_records: dict | None = None,
         device_codec: str = "off", device_source=None,
         submitted_at: float | None = None) -> dict:
    """Returns {"image_id", "stats", "records"}. ``prev_host_tree``
    (path->np array) enables delta8; ``parent`` links the incremental
    chain; ``reuse_records`` re-emits cached records for digest-proven
    unchanged leaves (the pre-dump residual path — see core/predump.py).
    ``executor`` defaults to the process-wide pipelined engine.
    ``chunking_mode``: "fixed" windows or "cdc" rolling-hash boundaries.
    ``device_codec`` ("off"/"auto"/"on") routes codec-applied fp32 leaves
    through the fused device encode+digest stage (core/device_codec.py),
    double-buffered against the host chunk writes; ``device_source`` is
    the original (possibly device-resident) tree so encode reads HBM
    directly — defaults to ``tree``. The whole body is a ``ckpt.dump``
    span; an async job passes ``submitted_at`` (``time.perf_counter`` at
    submit), and the span records ``queued_s``, submit to start."""
    with tracing.span("ckpt.dump", rss=True, step=int(step)) as sp:
        if submitted_at is not None:
            sp.set(queued_s=sp.t0 - submitted_at)
        tier = as_tier(root)
        replicas = [as_tier(r) for r in replicas]
        ex = executor or get_default_executor()

        host = capture(tree)
        leaves = flatten_with_paths(host)
        plan = plan_dump(leaves, step=step, image_id=image_id,
                         parent=parent, codec_policy=codec_policy,
                         prev_host_tree=prev_host_tree,
                         chunk_bytes=chunk_bytes,
                         chunking=chunking_mode,
                         process_index=process_index,
                         num_processes=num_processes,
                         reuse_records=reuse_records)
        sp.set(image_id=plan.image_id)

        encoded = None
        if device_codec_mod.resolve_mode(device_codec):
            src = dict(flatten_with_paths(
                device_source if device_source is not None else host))
            encoded = device_codec_mod.encode_leaves(
                plan, src, prev_host_tree, ex)

        arrays = {p: np.asarray(a) for p, a in leaves}
        # the writer guard spans probe->write->commit: a concurrent gc on
        # the SAME tier object (sessions sharing a mem://, remote:// or
        # cache+remote:// URI) waits here instead of reaping chunks this
        # dump has written but not yet referenced from a committed manifest
        with tier.writer():
            out = ex.run_dump(plan, arrays, tier, replicas,
                              prev_host_tree=prev_host_tree, encoded=encoded)

            man = manifest.build(plan.image_id, step=step,
                                 leaves=out["records"],
                                 meta=meta or {}, parent=parent,
                                 env=manifest.env_fingerprint(),
                                 topology=topology)
            journal = tier.ref_journal()
            if journal is not None and num_processes == 1:
                # refcount journal entry lands BEFORE the manifest (both
                # inside the writer guard): a crash between the two leaves
                # an orphan ref (bounded leak, swept later), never a
                # committed manifest whose chunks a peer job's gc may reap
                journal.publish(
                    plan.image_id,
                    {h for rec in out["records"] for h in rec["chunks"]},
                    manifest_rel=tier.manifest_path(plan.image_id))
            if num_processes > 1:
                part = (f"images/{plan.image_id}/"
                        f"manifest.part{process_index}.json")
                tier.write_bytes(part, manifest.to_json(man))
                if process_index == 0:
                    merge_parts(tier, plan.image_id, num_processes,
                                replicas=replicas)
            else:
                blob = manifest.to_json(man)
                tier.write_bytes(tier.manifest_path(plan.image_id), blob,
                                 atomic=True)
                for r in replicas:
                    r.write_bytes(r.manifest_path(plan.image_id), blob,
                                  atomic=True)
        return {"image_id": plan.image_id, "stats": out["stats"],
                "records": man["leaves"]}


def merge_parts(tier: Tier, image_id: str, num_processes: int, replicas=()):
    """Process 0 merges per-process manifest parts into the final manifest
    (commit point for the whole distributed dump — the 'global barrier')."""
    parts = []
    for k in range(num_processes):
        raw = tier.read_bytes(f"images/{image_id}/manifest.part{k}.json")
        parts.append(json.loads(raw))
    base = parts[0]
    leaves = []
    for p in parts:
        leaves.extend(p["leaves"])
    leaves.sort(key=lambda r: r["path"])
    man = manifest.build(image_id, step=base["step"], leaves=leaves,
                         meta=base["meta"], parent=base["parent"],
                         env=base["env"], topology=base["topology"])
    journal = tier.ref_journal()
    if journal is not None:
        # the merged manifest is the whole distributed image — publish
        # its full chunk set before the commit point (same crash
        # ordering as the single-process path)
        journal.publish(image_id,
                        {h for r in leaves for h in r["chunks"]},
                        manifest_rel=tier.manifest_path(image_id))
    blob = manifest.to_json(man)
    tier.write_bytes(tier.manifest_path(image_id), blob, atomic=True)
    for r in replicas:
        r.write_bytes(r.manifest_path(image_id), blob, atomic=True)


def host_tree_by_path(tree) -> dict:
    """Snapshot {path: np.ndarray} — kept by callers that use delta8."""
    return {p: np.asarray(a) for p, a in flatten_with_paths(
        jax.device_get(tree))}
