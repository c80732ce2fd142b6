"""Device-side codec stage (core/device_codec.py): the fused encode+digest
path must be a drop-in for the host codec — byte-identical stored buffers,
device failures that fail the dump, digest verification that trips on
corrupted payloads, and mode/eligibility gating."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CheckpointSession, CodecPolicy, DumpRequest,
                       RestoreRequest, SessionConfig)
from repro.core import device_codec as dc
from repro.core.compression import decode_leaf, encode_leaf
from repro.core.integrity import CorruptionError
from repro.core.plan import plan_dump
from repro.kernels.ckpt_codec import ops

N = dc.DEVICE_MIN_BYTES // 4 + 101      # eligible and non-multiple-of-block


def leaf_pair(seed=0, n=N):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    prev = x + rng.standard_normal(n).astype(np.float32) * 0.01
    return x, prev


def delta_plan(x, prev, path="opt/m/w"):
    return plan_dump([(path, x)], step=0,
                     codec_policy=lambda p: "delta8",
                     prev_host_tree={path: prev})


# ---------------------------------------------------------- mode resolution
def test_resolve_mode():
    assert dc.resolve_mode("off") is False
    assert dc.resolve_mode(None) is False
    assert dc.resolve_mode(False) is False
    assert dc.resolve_mode("on") is True
    assert dc.resolve_mode(True) is True
    # auto: on only with an accelerator backend (CPU in CI -> off)
    expect = jax.default_backend() in ("tpu", "gpu")
    assert dc.resolve_mode("auto") is expect
    with pytest.raises(ValueError, match="unknown device codec mode"):
        dc.resolve_mode("maybe")


def test_eligibility_gates():
    x, prev = leaf_pair()
    plan = delta_plan(x, prev)
    (lp,) = plan.leaves
    assert dc.eligible(lp)
    # too small: dispatch overhead beats the fused win
    small = plan_dump([("opt/m/w", x[:16])], step=0,
                      codec_policy=lambda p: "delta8",
                      prev_host_tree={"opt/m/w": prev[:16]})
    assert not dc.eligible(small.leaves[0])
    # no baseline -> delta8 not applied -> host path
    nobase = plan_dump([("opt/m/w", x)], step=0,
                       codec_policy=lambda p: "delta8")
    assert not dc.eligible(nobase.leaves[0])
    # raw leaves stay on the host
    raw = plan_dump([("params/w", x)], step=0)
    assert not dc.eligible(raw.leaves[0])


# ----------------------------------------------------- stage parity / digest
@pytest.mark.parametrize("codec", ["delta8", "bf16"])
def test_stage_stored_bytes_match_host_codec(codec):
    x, prev = leaf_pair(1)
    if codec == "delta8":
        plan, prev_tree = delta_plan(x, prev), {"opt/m/w": prev}
        path = "opt/m/w"
    else:
        plan = plan_dump([("opt/m/w", x)], step=0,
                         codec_policy=lambda p: "bf16")
        prev_tree, path = {}, "opt/m/w"
    futs = dc.encode_leaves(plan, {path: x}, prev_tree)
    stored_dev, meta_dev = futs[path].result()
    stored_host, meta_host = encode_leaf(
        x, codec, prev if codec == "delta8" else None)
    np.testing.assert_array_equal(
        np.ascontiguousarray(stored_dev).view(np.uint8).reshape(-1),
        np.ascontiguousarray(stored_host).view(np.uint8).reshape(-1))
    assert meta_dev["encoder"] == "device"
    assert meta_dev["digest_alg"] == ops.DIGEST_ALG
    # meta is a superset of the host meta (digest fields on top)
    for k, v in meta_host.items():
        assert meta_dev[k] == v
    # decode verifies the digest and round-trips within codec error
    back = decode_leaf(stored_dev, codec, meta_dev,
                       prev if codec == "delta8" else None)
    assert float(np.max(np.abs(np.asarray(back, np.float32).reshape(-1)
                               - x))) < 1e-2


def test_corrupted_payload_trips_digest_on_decode():
    x, prev = leaf_pair(2)
    futs = dc.encode_leaves(delta_plan(x, prev), {"opt/m/w": x},
                            {"opt/m/w": prev})
    stored, meta = futs["opt/m/w"].result()
    bad = stored.copy()
    bad[len(bad) // 2] ^= 1
    with pytest.raises(CorruptionError, match="payload digest mismatch"):
        decode_leaf(bad, "delta8", meta, prev)


def test_device_failure_propagates_out_of_dump(monkeypatch, tmp_path):
    """No host fallback: an injected device failure fails the dump with
    the original error and commits no image."""
    x, prev = leaf_pair(3)

    def boom(*a, **kw):
        raise RuntimeError("injected device fault")

    monkeypatch.setattr(ops, "delta_encode_digest", boom)
    with pytest.raises(RuntimeError, match="injected device fault"):
        dc.encode_leaves(delta_plan(x, prev), {"opt/m/w": x},
                         {"opt/m/w": prev})["opt/m/w"].result()

    t1, t2 = tree_pair()
    sess = CheckpointSession(SessionConfig(
        root=str(tmp_path / "ck"),
        codec=CodecPolicy(optimizer="delta8", device="on")))
    first = sess.dump(DumpRequest(state=t1, step=1))   # no baseline yet
    with pytest.raises(RuntimeError, match="injected device fault"):
        sess.dump(DumpRequest(state=t2, step=2))
    assert sess.registry.latest()["image_id"] == first.image_id


# ------------------------------------------------------------- end to end
def tree_pair():
    x, prev = leaf_pair(4, N)
    t1 = {"params": {"w": jnp.asarray(x)},
          "opt": {"m": {"w": jnp.asarray(prev)}},
          "step": jnp.asarray(1, jnp.int32)}
    t2 = jax.tree.map(lambda v: v + 0.01 if v.dtype == jnp.float32 else v,
                      t1)
    return t1, t2


@pytest.mark.parametrize("serial", [False, True])
def test_dump_restore_bit_identical_across_device_modes(tmp_path, serial):
    """The hard invariant: device="on" restores are bit-identical to
    device="off" restores (delta8 is lossy, so the oracle is the host
    codec, not the original tree)."""
    t1, t2 = tree_pair()
    out = {}
    for mode in ("off", "on"):
        sess = CheckpointSession(SessionConfig(
            root=str(tmp_path / mode), serial=serial,
            codec=CodecPolicy(params="bf16", optimizer="delta8",
                              device=mode)))
        sess.dump(DumpRequest(state=t1, step=1))
        r = sess.dump(DumpRequest(state=t2, step=2))
        if mode == "on":
            assert r.stats["leaves_device"] > 0
        out[mode] = sess.restore(RestoreRequest()).state
    for a, b in zip(jax.tree.leaves(out["off"]), jax.tree.leaves(out["on"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_device_records_carry_digest_and_verify_on_restore(tmp_path):
    t1, t2 = tree_pair()
    sess = CheckpointSession(SessionConfig(
        root=str(tmp_path / "ck"),
        codec=CodecPolicy(optimizer="delta8", device="on")))
    sess.dump(DumpRequest(state=t1, step=1))
    r = sess.dump(DumpRequest(state=t2, step=2))
    from repro.core.restore import read_manifest
    leaves = read_manifest(sess.tier, r.image_id)["leaves"]
    dev = [rec for rec in leaves
           if rec.get("codec_meta", {}).get("encoder") == "device"]
    assert dev, "no device-encoded leaf records in the manifest"
    for rec in dev:
        assert rec["codec_meta"]["digest_alg"] == ops.DIGEST_ALG
        assert len(rec["codec_meta"]["digest"]) == 16
    # restore exercises decode_leaf's digest re-verification path
    res = sess.restore(RestoreRequest())
    for a, b in zip(jax.tree.leaves(res.state), jax.tree.leaves(t2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-2)
