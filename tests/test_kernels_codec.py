"""ckpt_codec Pallas kernel vs oracle: exact agreement, dirty flags, bounds,
and the fused encode+digest family (interpret-mode parity, digest fold /
re-verification)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline image: seeded fixed-example fallback
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.ckpt_codec import ops
from repro.kernels.ckpt_codec.ckpt_codec import (bf16_encode_digest_pallas,
                                                 delta_decode_pallas,
                                                 delta_encode_digest_pallas,
                                                 delta_encode_pallas,
                                                 digest_blocks_pallas)
from repro.kernels.ckpt_codec.ops import delta_decode, delta_encode
from repro.kernels.ckpt_codec.ref import (bf16_encode_digest_ref,
                                          delta_decode_ref,
                                          delta_encode_digest_ref,
                                          delta_encode_ref,
                                          digest_blocks_ref)

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


@pytest.mark.parametrize("nblk,blk", [(3, 256), (1, 128), (8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_equals_ref(nblk, blk, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (nblk, blk), dtype)
    prev = x + 0.01 * jax.random.normal(k2, (nblk, blk), dtype)
    qp, sp, dp = delta_encode_pallas(x, prev, interpret=True)
    qr, sr, dr = delta_encode_ref(x, prev)
    assert bool(jnp.all(qp == qr))
    assert bool(jnp.allclose(sp, sr))
    assert bool(jnp.all(dp == dr))
    xp = delta_decode_pallas(qp, sp, prev, interpret=True)
    xr = delta_decode_ref(qr, sr, prev)
    assert float(jnp.abs(xp.astype(jnp.float32)
                         - xr.astype(jnp.float32)).max()) < 1e-6


def test_clean_blocks_exact_and_flagged():
    x = jnp.ones((4, 64), jnp.float32)
    prev = x.at[2].add(0.5)
    q, s, d = delta_encode_ref(x, prev)
    assert d.tolist() == [False, False, True, False]
    out = delta_decode_ref(q, s, prev)
    assert bool(jnp.all(out[jnp.array([0, 1, 3])] == 1.0))


# ------------------------------------------------ fused encode+digest family
@pytest.mark.parametrize("nblk,blk", [(3, 256), (1, 128), (8, 512)])
def test_fused_delta_digest_pallas_equals_ref(nblk, blk):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (nblk, blk), jnp.float32)
    prev = x + 0.01 * jax.random.normal(k2, (nblk, blk), jnp.float32)
    prev = prev.at[0].set(x[0])            # one exactly-clean block
    w = ops.digest_weights(blk)
    qp, sp, dp, h1p, h2p = delta_encode_digest_pallas(x, prev, w,
                                                      interpret=True)
    qr, sr, dr, h1r, h2r = delta_encode_digest_ref(x, prev, w)
    assert bool(jnp.all(qp == qr)) and bool(jnp.all(dp == dr))
    assert bool(jnp.allclose(sp, sr))
    assert h1p.dtype == jnp.uint32 and bool(jnp.all(h1p == h1r))
    assert bool(jnp.all(h2p == h2r))
    # a clean block's payload is all-zero int8 -> both lanes are zero
    assert int(h1p[0]) == 0 and int(h2p[0]) == 0
    assert not dp[0]


@pytest.mark.parametrize("nblk,blk", [(3, 256), (1, 128)])
def test_fused_bf16_digest_pallas_equals_ref(nblk, blk):
    x = jax.random.normal(jax.random.PRNGKey(2), (nblk, blk), jnp.float32)
    w = ops.digest_weights(blk)
    yp, h1p, h2p = bf16_encode_digest_pallas(x, w, interpret=True)
    yr, h1r, h2r = bf16_encode_digest_ref(x, w)
    assert yp.dtype == jnp.bfloat16
    assert bool(jnp.all(jax.lax.bitcast_convert_type(yp, jnp.uint16)
                        == jax.lax.bitcast_convert_type(yr, jnp.uint16)))
    assert bool(jnp.all(h1p == h1r)) and bool(jnp.all(h2p == h2r))


def test_partial_last_grid_step_matches_ref():
    """nblk not a multiple of the kernels' block group: the last grid step
    is partial and its out-of-range rows must not leak into the result."""
    nblk, blk = 11, 256
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(k1, (nblk, blk), jnp.float32)
    prev = x + 0.01 * jax.random.normal(k2, (nblk, blk), jnp.float32)
    w = ops.digest_weights(blk)
    got = delta_encode_digest_pallas(x, prev, w, interpret=True)
    want = delta_encode_digest_ref(x, prev, w)
    for a, b in zip(got, want):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    yp, h1p, h2p = bf16_encode_digest_pallas(x, w, interpret=True)
    yr, h1r, h2r = bf16_encode_digest_ref(x, w)
    assert bool(jnp.all(yp == yr)) and bool(jnp.all(h1p == h1r))
    assert bool(jnp.all(h2p == h2r))
    xp = delta_decode_pallas(got[0], got[1], prev, interpret=True)
    xr = delta_decode_ref(want[0], want[1], prev)
    assert float(jnp.abs(xp - xr).max()) < 1e-6


def test_digest_blocks_pallas_equals_ref():
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 256), jnp.float32)
    w = ops.digest_weights(256)
    h1p, h2p = digest_blocks_pallas(x, w, interpret=True)
    h1r, h2r = digest_blocks_ref(x, w)
    assert bool(jnp.all(h1p == h1r)) and bool(jnp.all(h2p == h2r))


@pytest.mark.parametrize("n", [1, 100, 257, 1000, 16384, 20000])
def test_fused_ops_ragged_shapes_match_host_codec(n):
    """The jitted ops wrappers pad non-multiple-of-block flat arrays; the
    stored layout they imply must stay byte-identical to the host
    encode_leaf for any length, and payload_digest must re-derive the
    folded digest from the stored bytes alone."""
    from repro.core.compression import CODEC_BLOCK, encode_leaf
    rng = np.random.default_rng(n)
    x = rng.standard_normal(n).astype(np.float32)
    prev = x + rng.standard_normal(n).astype(np.float32) * 0.1

    q, s, _d, h1, h2 = ops.delta_encode_digest(
        jnp.asarray(x), jnp.asarray(prev), block=CODEC_BLOCK)
    q, s, h1, h2 = (np.asarray(a) for a in (q, s, h1, h2))
    stored_dev = np.concatenate([s.view(np.int8).reshape(-1),
                                 q.reshape(-1)])
    stored_host, _ = encode_leaf(x, "delta8", prev)
    np.testing.assert_array_equal(
        stored_dev.view(np.uint8),
        np.ascontiguousarray(stored_host).view(np.uint8).reshape(-1))
    dig = ops.fold_digest(h1, h2, scale_bits=s, n=n)
    meta = {"block": CODEC_BLOCK, "nblk": int(q.shape[0]),
            "orig_shape": [n]}
    assert ops.payload_digest(stored_dev, "delta8", meta) == dig

    y, b1, b2 = ops.bf16_encode_digest(jnp.asarray(x), block=CODEC_BLOCK)
    stored_bf = np.asarray(y).reshape(-1)[:n]
    host_bf, _ = encode_leaf(x, "bf16", None)
    np.testing.assert_array_equal(
        np.ascontiguousarray(stored_bf).view(np.uint16),
        np.ascontiguousarray(host_bf).view(np.uint16).reshape(-1))
    digb = ops.fold_digest(np.asarray(b1), np.asarray(b2), n=n)
    assert ops.payload_digest(stored_bf, "bf16",
                              {"block": CODEC_BLOCK}) == digb


def test_payload_digest_trips_on_corruption():
    rng = np.random.default_rng(7)
    x = rng.standard_normal(512).astype(np.float32)
    prev = x + 0.1
    q, s, _d, h1, h2 = ops.delta_encode_digest(
        jnp.asarray(x), jnp.asarray(prev), block=256)
    q, s, h1, h2 = (np.asarray(a) for a in (q, s, h1, h2))
    stored = np.concatenate([s.view(np.int8).reshape(-1), q.reshape(-1)])
    meta = {"block": 256, "nblk": 2, "orig_shape": [512]}
    dig = ops.fold_digest(h1, h2, scale_bits=s, n=512)
    assert ops.payload_digest(stored, "delta8", meta) == dig
    bad = stored.copy()
    bad[-1] ^= 1                       # flip one payload bit
    assert ops.payload_digest(bad, "delta8", meta) != dig
    with pytest.raises(ValueError, match="no payload digest"):
        ops.payload_digest(stored, "none", {})


@given(st.integers(min_value=1, max_value=3000),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_ops_padding_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    prev = jnp.asarray(rng.standard_normal(n), jnp.float32)
    q, s, d = delta_encode(x, prev, block=256)
    out = delta_decode(q, s, prev, n=n)
    assert out.shape == (n,)
    scale_per_elem = jnp.repeat(s, 256)[:n]
    assert bool(jnp.all(jnp.abs(out - x) <= scale_per_elem / 2 * 1.001 + 1e-7))
