"""The tree digest's two schemes (repro.core.integrity): the block scheme
(``blk1:``, 4 MiB blocks hashed in place on the digest pool)
against a plain reference of its definition, the legacy untagged scheme
against the single-stream algorithm it replaced, and resume verifying an
image under whichever scheme its record carries."""
import hashlib
import importlib

import ml_dtypes
import numpy as np
import pytest

from repro.api import CheckpointSession, DumpRequest, SessionConfig
from repro.core import (CorruptionError, MigrationManifest, resume,
                        tracing, train_meta)
from repro.core.integrity import tree_digest, tree_digest_like

BLOCK = 4 << 20


def block_reference(pairs: dict) -> str:
    """The block scheme written out plainly: per leaf in path order the
    length-prefixed path, dtype and shape, the byte count, then the
    SHA-256 of each consecutive 4 MiB block of its C-order bytes."""
    root = hashlib.sha256()
    for path in sorted(pairs):
        a = np.asarray(pairs[path])
        data = a.tobytes(order="C")
        for field in (path, str(a.dtype), repr(a.shape)):
            b = field.encode()
            root.update(len(b).to_bytes(8, "little") + b)
        root.update(len(data).to_bytes(8, "little"))
        for i in range(0, len(data), BLOCK):
            root.update(hashlib.sha256(data[i:i + BLOCK]).digest())
    return "blk1:" + root.hexdigest()


def legacy_reference(pairs: dict) -> str:
    """The single-stream digest images were written with before the block
    scheme: path, dtype, shape and a `tobytes` copy, in path order."""
    h = hashlib.sha256()
    for path in sorted(pairs):
        a = np.asarray(pairs[path])
        h.update(path.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def leaf(kind: str):
    rng = np.random.default_rng(len(kind))
    if kind == "scalar":
        return np.int32(7)
    if kind == "empty":
        return np.zeros((0, 5), np.float32)
    if kind == "one_block":
        return rng.standard_normal(BLOCK // 4).astype(np.float32)
    if kind == "one_block_plus_byte":
        return rng.integers(0, 255, BLOCK + 1, dtype=np.uint8)
    if kind == "multi_block_f32":
        return rng.standard_normal((3, 900_000)).astype(np.float32)
    if kind == "bf16":
        return rng.standard_normal((1500, 1700)).astype(ml_dtypes.bfloat16)
    if kind == "transposed":
        return rng.standard_normal((1100, 1300)).astype(np.float32).T
    raise ValueError(kind)


KINDS = ["scalar", "empty", "one_block", "one_block_plus_byte",
         "multi_block_f32", "bf16", "transposed"]


@pytest.fixture(autouse=True)
def fresh():
    tracing.reset()
    yield
    tracing.reset()


@pytest.mark.parametrize("kind", KINDS)
def test_block_digest_equals_reference(kind):
    a = leaf(kind)
    if kind == "transposed":
        assert not a.flags.c_contiguous
    tree = {"w": a, "step": np.int32(1)}
    assert tree_digest(tree) == block_reference(tree)
    assert tree_digest_like(tree, block_reference(tree)) == tree_digest(tree)


@pytest.mark.parametrize("kind", KINDS)
def test_legacy_digest_equals_the_single_stream_algorithm(kind):
    tree = {"w": leaf(kind), "step": np.int32(1)}
    want = legacy_reference(tree)
    assert len(want) == 64 and ":" not in want
    assert tree_digest_like(tree, want) == want
    (sp,) = tracing.spans()
    assert sp["attrs"]["scheme"] == "legacy" and sp["attrs"]["blocks"] == 0


def test_pool_digest_equals_reference():
    """A 19 MiB tree fans out over the digest pool, one count per block."""
    tree = {k: leaf(k) for k in ("multi_block_f32", "one_block_plus_byte",
                                 "bf16", "scalar")}
    assert tree_digest(tree) == block_reference(tree)
    (sp,) = tracing.spans()
    n = 3 + 2 + 2 + 1           # 10.8 MB, 4 MiB + 1 byte, 5.1 MB, a scalar
    assert sp["attrs"]["blocks"] == n
    c = sp["counters"]["digest_block"]
    assert c["count"] == n and c["bytes"] == sp["attrs"]["bytes"]


def golden_tree() -> dict:
    """A fixed tree with a leaf past one block, so the digest pins the
    block size as well as the tag and the field encoding."""
    return {"a": np.arange(1_200_000, dtype=np.float32),
            "b": np.linspace(-2, 2, 60, dtype=np.float32)
            .astype(ml_dtypes.bfloat16).reshape(6, 10),
            "c/step": np.int32(7)}


@pytest.mark.parametrize("scheme, want", [
    ("blk1", "blk1:526546af03f1a9b09c1194be672dae06"
             "ca0379b23db38a4d43200459f0914453"),
    ("legacy", "b4b11edd7e69276bbc2eda6e400a9eac"
               "ab2d25855d104a3a514c0759f48e5188"),
])
def test_digest_of_a_fixed_tree_is_pinned(scheme, want):
    """A recorded digest must verify for as long as its scheme exists:
    changing the block size, the tag or the encoding fails here."""
    tree = golden_tree()
    if scheme == "blk1":
        assert tree_digest(tree) == want == block_reference(tree)
    else:
        assert tree_digest_like(tree, want) == want == legacy_reference(tree)


def test_digest_is_sensitive_to_every_bound_field():
    base = {"w": leaf("multi_block_f32"), "step": np.int32(1)}
    d = tree_digest(base)
    w = base["w"].copy()
    w.view(np.uint8).reshape(-1)[-1] ^= 1             # last byte, last block
    variants = [{**base, "w": w},
                {**base, "w": base["w"].reshape(900_000, 3)},     # shape
                {**base, "w": base["w"].view(np.int32)},          # dtype
                {"v": base["w"], "step": np.int32(1)},            # path
                {**base, "x": np.zeros(0, np.float32)}]           # a leaf
    assert len({d, *(tree_digest(v) for v in variants)}) == 6
    with pytest.raises(ValueError, match="scheme"):
        tree_digest_like(base, "blk9:" + "0" * 64)


def save_image(root: str, state: dict, digest: str):
    rec = MigrationManifest(step=1, arch="t", host_count=1, dp_degree=1,
                            state_digest=digest)
    meta = train_meta(arch="t", step=1, data_state={})
    meta["migration"] = rec.to_meta()
    CheckpointSession(SessionConfig(root=root)).dump(
        DumpRequest(state=state, step=1, meta=meta))


def flip_one_byte(monkeypatch):
    """Restore's decoded pairs come back with one byte changed, past the
    chunk check: only the tree digest can see it."""
    restore_mod = importlib.import_module("repro.core.restore")
    real = restore_mod.restore

    def tampered(*args, **kw):
        tree, man, pairs = real(*args, **kw)
        w = np.array(pairs["w"])
        w.view(np.uint8).reshape(-1)[12345] ^= 0x40
        return tree, man, {**pairs, "w": w}

    monkeypatch.setattr(restore_mod, "restore", tampered)


@pytest.mark.parametrize("scheme", ["legacy", "blk1"])
def test_resume_verifies_the_recorded_scheme(tmp_path, monkeypatch, scheme):
    state = {"w": leaf("multi_block_f32"), "b": leaf("bf16"),
             "step": np.int32(1)}
    digest = legacy_reference(state) if scheme == "legacy" \
        else tree_digest(state)
    save_image(str(tmp_path / "ck"), state, digest)
    tracing.reset()
    rep = resume(str(tmp_path / "ck"))
    assert rep.digest_verified is True
    assert rep.migration.state_digest == digest
    assert [s["attrs"]["scheme"] for s in tracing.spans()
            if s["name"] == "ckpt.tree_digest"] == [scheme]
    flip_one_byte(monkeypatch)
    with pytest.raises(CorruptionError, match="state digest"):
        resume(str(tmp_path / "ck"))


def test_lazy_resume_verifies_a_legacy_record(tmp_path):
    state = {"w": leaf("one_block_plus_byte"), "step": np.int32(1)}
    save_image(str(tmp_path / "ck"), state, legacy_reference(state))
    rep = resume(str(tmp_path / "ck"), lazy=True)
    assert rep.state.server.verify_tree_digest() is True
    rep = resume(str(tmp_path / "ck"), lazy=True)
    rep.state.server.expected_digest = legacy_reference(
        {**state, "step": np.int32(2)})
    with pytest.raises(CorruptionError, match="state digest"):
        rep.state.server.verify_tree_digest()
