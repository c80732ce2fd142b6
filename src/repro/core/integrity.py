"""Integrity: content hashing, verification, replica repair.

Every chunk is content-addressed by SHA-256; the manifest carries the hash
list per leaf plus its own digest. Restore verifies every chunk it reads;
on mismatch/missing it repairs from a replica tier (the paper's network-
file-system row, plus protection CRIU does not attempt)."""
from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.core import tracing

log = logging.getLogger(__name__)


def sha256(data) -> str:
    return hashlib.sha256(data).hexdigest()


def read_chunk_verified(tier, replicas, h: str, image_id: str) -> bytes:
    """Content-addressed read with verification + replica repair. Counts
    ``chunk_read`` and ``chunk_verify`` (core/tracing.py) per source tried."""
    sources = [tier] + list(replicas)
    for k, src in enumerate(sources):
        t0 = time.perf_counter()
        try:
            data = src.read_chunk(h)
        except FileNotFoundError:
            continue
        t1 = time.perf_counter()
        tracing.count("chunk_read", 1, len(data), t1 - t0)
        ok = sha256(data) == h
        tracing.count("chunk_verify", 1, len(data), time.perf_counter() - t1)
        if ok:
            if k > 0:  # repair the primary from the replica (overwrite the
                # corrupt file — bypass the content-addressed dedup check)
                tier.write_bytes(tier.chunk_path(h), data)
                tier.note_chunk_present(h)
                log.warning("repaired chunk %s from replica %d", h[:12], k)
            return data
        log.warning("chunk %s corrupt in source %d", h[:12], k)
    raise KeyError(h)


def manifest_digest(manifest_dict: dict) -> str:
    body = {k: v for k, v in manifest_dict.items() if k != "digest"}
    return sha256(json.dumps(body, sort_keys=True).encode())


# the block scheme's tag; a digest without a tag is the legacy scheme
BLOCK_SCHEME = "blk1"
# the block size is part of the blk1 format, apart from the chunker's
# tunable chunk size: changing it would make every recorded blk1 digest
# fail its check
_BLOCK = 4 << 20
_WORKERS = min(8, os.cpu_count() or 4)    # CheckpointExecutor's cpu rule
_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def _digest_pool() -> ThreadPoolExecutor:
    """The block hashers, private to the digest: tree_digest is called
    from tasks on the executor's pools, and waiting on a pool from inside
    it can deadlock."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(_WORKERS,
                                       thread_name_prefix="ckpt-digest")
        return _pool


def _leaves(pairs) -> list:
    """(path, array, its C-order bytes as a flat uint8 view) in path
    order; only a non-contiguous leaf is copied."""
    if isinstance(pairs, dict):
        pairs = pairs.items()
    out = []
    for path, arr in sorted(pairs, key=lambda kv: kv[0]):
        a = np.asarray(arr)
        out.append((path, a, np.ascontiguousarray(a).reshape(-1)
                    .view(np.uint8)))
    return out


def _hash_block(block) -> bytes:
    t0 = time.perf_counter()
    d = hashlib.sha256(block).digest()
    tracing.count("digest_block", 1, block.nbytes, time.perf_counter() - t0)
    return d


def _block_digest(leaves, sp) -> str:
    blocks = [buf[i:i + _BLOCK] for _, _, buf in leaves
              for i in range(0, buf.nbytes, _BLOCK)]
    pool = _digest_pool()
    digests = [f.result() for f in
               [tracing.submit(pool, _hash_block, b) for b in blocks]]
    sp.set(blocks=len(blocks))
    root, k = hashlib.sha256(), 0
    for path, a, buf in leaves:
        for field in (path, str(a.dtype), repr(a.shape)):
            b = field.encode()
            root.update(len(b).to_bytes(8, "little"))
            root.update(b)
        root.update(buf.nbytes.to_bytes(8, "little"))
        n = -(-buf.nbytes // _BLOCK)
        root.update(b"".join(digests[k:k + n]))
        k += n
    return f"{BLOCK_SCHEME}:{root.hexdigest()}"


def _legacy_digest(leaves, sp) -> str:
    h = hashlib.sha256()
    for path, a, buf in leaves:
        h.update(path.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(buf)
    sp.set(blocks=0)
    return h.hexdigest()


def _tree_digest(pairs, scheme: str) -> str:
    with tracing.span("ckpt.tree_digest", scheme=scheme) as sp:
        leaves = _leaves(pairs)
        sp.set(bytes=sum(buf.nbytes for _, _, buf in leaves))
        if scheme == BLOCK_SCHEME:
            return _block_digest(leaves, sp)
        return _legacy_digest(leaves, sp)


def tree_digest(pairs) -> str:
    """Logical-state digest: hash of (path, dtype, shape, bytes) over the
    leaves in path order. Topology-free by construction — the same logical
    values give the same digest no matter what mesh the tree lives on (or
    lived on), which is exactly the invariant a cross-topology migration
    must preserve. ``pairs`` is {path: array} or an iterable of
    (path, array).

    Two-level SHA-256, returned as ``blk1:<hex>``: each leaf's C-order
    bytes, viewed in place, are hashed in 4 MiB blocks on a private pool
    of min(8, cpus) threads; the root hashes, per leaf in path order, the
    length-prefixed path, dtype and shape, the byte count and the block
    digests in order. The value depends on the tree alone, never on the
    threads. Recorded as a ``ckpt.tree_digest`` span with ``bytes`` and
    ``blocks``; each block counts ``digest_block``."""
    return _tree_digest(pairs, BLOCK_SCHEME)


def tree_digest_like(pairs, recorded: str | None) -> str:
    """``pairs``' digest in the scheme of the ``recorded`` one, for
    checking it: the block scheme for a ``blk1:`` digest (or none), the
    legacy single-stream SHA-256 of (path, dtype, shape, bytes) for an
    untagged 64-hex one (images written before the block scheme)."""
    tag, sep, _ = (recorded or BLOCK_SCHEME + ":").partition(":")
    if not sep:
        return _tree_digest(pairs, "legacy")
    if tag != BLOCK_SCHEME:
        raise ValueError(f"unknown tree digest scheme {tag!r}")
    return _tree_digest(pairs, BLOCK_SCHEME)


class CorruptionError(RuntimeError):
    def __init__(self, image_id: str, bad_chunks: list):
        self.image_id = image_id
        self.bad_chunks = bad_chunks
        super().__init__(f"image {image_id}: {len(bad_chunks)} corrupt/missing "
                         f"chunks: {bad_chunks[:5]}{'...' if len(bad_chunks) > 5 else ''}")
