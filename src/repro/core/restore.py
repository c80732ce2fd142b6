"""criu-restore for JAX job state: plan, then execute.

plan_restore loads the manifest plus the whole delta8 ancestor chain once
(the seed path re-parsed the parent manifest for every delta8 leaf); the
CheckpointExecutor then verifies + assembles chunks in parallel (repairing
from replica tiers on corruption), decodes codecs with a memoized parent-
leaf cache, rebuilds the pytree and places it onto the TARGET mesh with the
TARGET shardings — cross-topology restore is just device_put with new
shardings, because images store abstract state, not device state (the
paper's rows 6/7/10, solved)."""
from __future__ import annotations

import logging

import jax

from repro.core import manifest, tracing
from repro.core.executor import CheckpointExecutor, get_default_executor
from repro.core.plan import plan_restore
from repro.core.storage import as_tier

log = logging.getLogger(__name__)


def read_manifest(tier, image_id: str) -> dict:
    return manifest.from_json(tier.read_bytes(tier.manifest_path(image_id)))


def latest_image_id(tier) -> str | None:
    ids = [i for i in tier.image_ids()
           if tier.exists(tier.manifest_path(i))]
    if not ids:
        return None
    best = max(ids, key=lambda i: read_manifest(tier, i)["step"])
    return best


def check_env(man: dict, allow_env_mismatch: bool = True):
    """Compare the image's recorded env fingerprint against this process;
    warn (the default — state is abstract) or raise on mismatch. Shared by
    the eager and lazy restore paths so the policy can't diverge."""
    env = manifest.env_fingerprint()
    for k, v in man["env"].items():
        if env.get(k) != v:
            msg = f"env mismatch on restore: {k}: image={v} here={env.get(k)}"
            if allow_env_mismatch:
                log.warning("%s (restoring anyway — state is abstract)", msg)
            else:
                raise RuntimeError(msg)


def _unflatten_paths(pairs: dict):
    """Rebuild nested dicts from 'a/b/c' paths (job state is dict-shaped)."""
    root: dict = {}
    for path, leaf in pairs.items():
        parts = path.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


def restore(root, image_id: str | None = None, *, target_struct=None,
            shardings=None, replicas=(), allow_env_mismatch: bool = True,
            executor: CheckpointExecutor | None = None,
            with_pairs: bool = False):
    """Returns (tree, manifest_dict), or (tree, manifest_dict, pairs) when
    ``with_pairs`` — the raw decoded {path: array} exactly as stored,
    before any target-dtype cast or device placement (what the migration
    layer digests to prove bit-identical logical state).

    target_struct: optional pytree of ShapeDtypeStructs — output matches its
    treedef and dtypes (checked). shardings: optional matching pytree of
    Shardings -> leaves are device_put onto the new topology."""
    tier = as_tier(root)
    replicas = [as_tier(r) for r in replicas]
    ex = executor or get_default_executor()
    image_id = image_id or latest_image_id(tier)
    if image_id is None:
        raise FileNotFoundError("no checkpoint images found")
    plan = plan_restore(tier, image_id)
    man = plan.manifest
    check_env(man, allow_env_mismatch)

    pairs = ex.run_restore(plan, tier, replicas)

    if target_struct is not None:
        flat = jax.tree_util.tree_flatten_with_path(target_struct)
        paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path) for path, _ in flat[0]]
        missing = [p for p in paths if p not in pairs]
        if missing:
            raise KeyError(f"checkpoint lacks leaves: {missing[:5]}")
        leaves = []
        with tracing.span("ckpt.cast") as sp:
            for p, (_, want) in zip(paths, flat[0]):
                arr = pairs[p]
                if tuple(arr.shape) != tuple(want.shape):
                    raise ValueError(
                        f"{p}: shape {arr.shape} != {want.shape}")
                leaves.append(arr.astype(want.dtype))
            sp.set(bytes=sum(a.nbytes for a in leaves))
        tree = jax.tree_util.tree_unflatten(flat[1], leaves)
    else:
        tree = _unflatten_paths(pairs)

    if shardings is not None:
        tree = jax.tree.map(lambda a, s: jax.device_put(a, s),
                            tree, shardings)
    if with_pairs:
        return tree, man, pairs
    return tree, man
