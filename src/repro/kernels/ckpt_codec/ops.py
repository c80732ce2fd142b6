"""jit'd wrappers: Pallas on TPU, jnp oracle elsewhere. Handles arbitrary
flat sizes by padding to whole blocks (padding encodes as clean and digests
as zero — padded units contribute 0 to the mult-acc lanes).

This module is the public door for the fused encode+digest kernel family
(core/device_codec.py and core/compression.py both come through here):

  delta_encode / delta_decode              plain codec (existing surface)
  delta_encode_digest / bf16_encode_digest fused encode + per-block digest
  digest_blocks                            digest-only sweep (classification)
  fold_digest                              per-block lanes -> leaf hex digest
  payload_digest                           numpy re-verification on decode

Digest algorithm ("pmac32x2-v1"): two uint32 polynomial multiply-accumulate
lanes over the encoded payload units of each block, weights r^(i+1) mod 2^32
for two distinct odd multipliers; per-block lane pairs are folded into one
64-bit leaf digest with a second polynomial pass that also binds the element
count (and, for delta8, the scale bit patterns). Wraparound uint32
arithmetic is bit-identical in numpy, jnp and Pallas, so the device encode
path and the host verifier can never drift."""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.ckpt_codec import ref
from repro.kernels.ckpt_codec.ckpt_codec import (bf16_encode_digest_pallas,
                                                 delta_decode_pallas,
                                                 delta_encode_digest_pallas,
                                                 delta_encode_pallas,
                                                 digest_blocks_pallas)

BLOCK = 16384  # fp32 elements per codec block: one (128, 128) kernel tile

DIGEST_ALG = "pmac32x2-v1"
_R1 = np.uint32(0x01000193)   # FNV-1 prime (odd -> invertible mod 2^32)
_R2 = np.uint32(0x5BD1E995)   # MurmurHash2 multiplier (odd, independent)


@functools.lru_cache(maxsize=8)
def _weights_np(block: int) -> np.ndarray:
    """[2, block] uint32: row k holds r_k^(i+1) mod 2^32."""
    w = np.empty((2, block), np.uint32)
    w[0] = np.cumprod(np.full(block, _R1, np.uint32), dtype=np.uint32)
    w[1] = np.cumprod(np.full(block, _R2, np.uint32), dtype=np.uint32)
    return w


def digest_weights(block: int = BLOCK):
    """The constant weight table the fused kernels take as an input."""
    return jnp.asarray(_weights_np(block))


def _blocked(flat, block):
    n = flat.shape[0]
    nblk = max(1, -(-n // block))
    pad = nblk * block - n
    return jnp.pad(flat, (0, pad)).reshape(nblk, block), pad


@functools.partial(jax.jit, static_argnames=("block", "impl", "interpret"))
def delta_encode(x, prev, *, block=BLOCK, impl="auto", interpret=False):
    """Flat arrays (any length) -> (q int8 [nblk,block], scale [nblk],
    dirty [nblk]). Padding beyond len(x) is clean by construction."""
    assert x.shape == prev.shape and x.ndim == 1
    xb, _ = _blocked(x, block)
    pb, _ = _blocked(prev, block)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "pallas":
        return delta_encode_pallas(xb, pb, interpret=interpret)
    return ref.delta_encode_ref(xb, pb)


@functools.partial(jax.jit, static_argnames=("n", "impl", "interpret"))
def delta_decode(q, scale, prev, *, n=None, impl="auto", interpret=False):
    """Inverse of delta_encode; returns flat array of length n."""
    block = q.shape[1]
    pb, _ = _blocked(prev, block)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "pallas":
        xb = delta_decode_pallas(q, scale, pb, interpret=interpret)
    else:
        xb = ref.delta_decode_ref(q, scale, pb)
    flat = xb.reshape(-1)
    return flat[:n] if n is not None else flat


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return impl


@functools.partial(jax.jit, static_argnames=("block", "impl", "interpret"))
def delta_encode_digest(x, prev, *, block=BLOCK, impl="auto",
                        interpret=False):
    """Fused delta8 encode + per-block payload digest in one pass.
    Flat arrays (any length) -> (q int8 [nblk,block], scale f32 [nblk],
    dirty bool [nblk], h1 uint32 [nblk], h2 uint32 [nblk])."""
    assert x.shape == prev.shape and x.ndim == 1
    xb, _ = _blocked(x, block)
    pb, _ = _blocked(prev, block)
    w = digest_weights(block)
    if _resolve_impl(impl) == "pallas":
        return delta_encode_digest_pallas(xb, pb, w, interpret=interpret)
    return ref.delta_encode_digest_ref(xb, pb, w)


@functools.partial(jax.jit, static_argnames=("block", "impl", "interpret"))
def bf16_encode_digest(x, *, block=BLOCK, impl="auto", interpret=False):
    """Fused fp32 -> bf16 cast + per-block bit-pattern digest. Flat array
    (any length) -> (y bf16 [nblk,block] — caller slices to length,
    h1 uint32 [nblk], h2 uint32 [nblk])."""
    assert x.ndim == 1
    xb, _ = _blocked(x, block)
    w = digest_weights(block)
    if _resolve_impl(impl) == "pallas":
        return bf16_encode_digest_pallas(xb, w, interpret=interpret)
    return ref.bf16_encode_digest_ref(xb, w)


@functools.partial(jax.jit, static_argnames=("block", "impl", "interpret"))
def digest_blocks(x, *, block=BLOCK, impl="auto", interpret=False):
    """Digest-only sweep over a flat fp32 array -> (h1, h2) uint32 [nblk]."""
    assert x.ndim == 1
    xb, _ = _blocked(x, block)
    w = digest_weights(block)
    if _resolve_impl(impl) == "pallas":
        return digest_blocks_pallas(xb, w, interpret=interpret)
    return ref.digest_blocks_ref(xb, w)


# --------------------------------------------------- host-side fold / verify
def _powers(r: np.uint32, n: int) -> np.ndarray:
    return np.cumprod(np.full(n, r, np.uint32), dtype=np.uint32)


def fold_digest(h1, h2, scale_bits=None, *, n: int) -> str:
    """Fold per-block lane pairs (+ optional scale bit patterns) into the
    16-hex-char leaf digest stored in codec_meta. Pure numpy — runs on the
    host for both the device encode path and decode verification."""
    v1 = np.asarray(h1, np.uint32)
    v2 = np.asarray(h2, np.uint32)
    if scale_bits is not None:
        sb = np.asarray(scale_bits).view(np.uint32).reshape(-1)
        v1 = np.concatenate([v1, sb])
        v2 = np.concatenate([v2, sb])
    f1 = int(np.sum(v1 * _powers(_R1, len(v1)), dtype=np.uint32))
    f2 = int(np.sum(v2 * _powers(_R2, len(v2)), dtype=np.uint32))
    f1 = (f1 * int(_R1) + n) & 0xFFFFFFFF   # bind the element count
    f2 = (f2 * int(_R2) + n) & 0xFFFFFFFF
    return f"{f1:08x}{f2:08x}"


def _lanes_np(units: np.ndarray, block: int):
    """units: [nblk, block] uint32 -> per-block (h1, h2) — the numpy twin
    of the kernels' mult-acc, for decode-time re-verification."""
    w = _weights_np(block)
    h1 = np.sum(units * w[0][None, :], axis=1, dtype=np.uint32)
    h2 = np.sum(units * w[1][None, :], axis=1, dtype=np.uint32)
    return h1, h2


def payload_digest(stored: np.ndarray, codec: str, meta: dict) -> str:
    """Recompute the leaf digest from a *stored* (encoded) buffer — what
    decode_leaf checks against codec_meta["digest"]. Layouts mirror
    core/compression.py exactly."""
    block = int(meta.get("block", BLOCK))
    if codec == "delta8":
        flat = np.ascontiguousarray(stored).reshape(-1)
        nblk = int(meta["nblk"])
        scale = flat[:nblk * 4]
        units = flat[nblk * 4:].view(np.uint8).astype(
            np.uint32).reshape(nblk, block)
        h1, h2 = _lanes_np(units, block)
        n = int(np.prod(meta["orig_shape"], dtype=np.int64))
        return fold_digest(h1, h2, scale_bits=scale, n=n)
    if codec == "bf16":
        bits = np.ascontiguousarray(stored).view(np.uint16).reshape(-1)
        n = bits.size
        nblk = max(1, -(-n // block))
        padded = np.zeros(nblk * block, np.uint32)
        padded[:n] = bits
        h1, h2 = _lanes_np(padded.reshape(nblk, block), block)
        return fold_digest(h1, h2, n=n)
    raise ValueError(f"no payload digest for codec {codec!r} — raw leaves "
                     f"keep the blake2b classifier digest")
