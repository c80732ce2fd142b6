"""Device-side checkpoint codec: fused encode+digest on the accelerator,
double-buffered against host-side chunk writes.

The host dump hot path pays three passes per leaf (codec encode, serialize,
digest) before a byte reaches storage. This stage moves the first and last
onto the device with the fused kernels behind kernels/ckpt_codec/ops.py and
overlaps the device->host transfer with the executor's chunk writes:

    device encode leaf i+1   ||   device->host land leaf i   ||   chunk
                                                                  writes i-1

``encode_leaves`` dispatches the fused jitted encode for up to ``depth``
leaves before blocking on the oldest transfer (a bounded deque — the
double buffer), landing each result into a per-leaf Future the executor's
``do_leaf`` consumes in place of the host codec. On a serial engine the
pump runs inline before the dump (correct, no overlap). A device failure
fails the dump with the original error: there is no second codec to fall
back to on a TPU, where the host codec runs the same kernels.

Bit-identity contract: the stored buffer a landed Future carries is byte
for byte what ``core.compression.encode_leaf`` would have produced — the
kernels compute the same formulas in the same dtype, and the parity suite
(tests/test_device_codec.py) hard-asserts it. The only difference is
codec_meta: device-encoded leaves additionally carry the fused payload
digest ("pmac32x2-v1"), which decode_leaf re-verifies.
"""
from __future__ import annotations

from collections import deque
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.compression import CODEC_BLOCK
from repro.kernels.ckpt_codec import ops

DEVICE_CODEC_MODES = ("off", "auto", "on")
# below this a leaf's dispatch overhead beats the fused win; encode on host
DEVICE_MIN_BYTES = 1 << 16
DEPTH = 2            # double buffer: encodes in flight before landing


def resolve_mode(mode) -> bool:
    """CodecPolicy.device -> use the device stage? "auto" turns on only
    when an accelerator backend is present; "on" forces the fused path
    (XLA-on-CPU when no accelerator — the bench/test configuration)."""
    if mode in (None, False, "off"):
        return False
    if mode in (True, "on"):
        return True
    if mode == "auto":
        return jax.default_backend() in ("tpu", "gpu")
    raise ValueError(f"unknown device codec mode {mode!r}; "
                     f"choose from {DEVICE_CODEC_MODES}")


def eligible(lp) -> bool:
    """Which planned leaves the device stage takes: codec actually applied
    (delta8 with a baseline, or bf16 — both imply fp32 at plan time), not
    a pre-dump record re-emission, and big enough to beat dispatch cost."""
    return (lp.reuse is None
            and (lp.codec == "bf16"
                 or (lp.codec == "delta8" and lp.use_prev))
            and lp.nbytes >= DEVICE_MIN_BYTES)


def _land(lp, out):
    """Block on one device->host transfer and assemble the stored buffer
    + codec_meta, byte-identical to the host encode_leaf layouts."""
    host = jax.device_get(out)
    n = int(np.prod(lp.shape, dtype=np.int64))
    if lp.codec == "delta8":
        q, s, d, h1, h2 = (np.asarray(a) for a in host)
        stored = np.concatenate([s.view(np.int8).reshape(-1),
                                 q.reshape(-1)])
        meta = {"applied": True, "orig_dtype": "float32",
                "orig_shape": list(lp.shape),
                "block": CODEC_BLOCK, "nblk": int(q.shape[0]),
                "dirty_blocks": int(d.sum()),
                "digest": ops.fold_digest(h1, h2, scale_bits=s, n=n),
                "digest_alg": ops.DIGEST_ALG, "encoder": "device"}
        return stored, meta
    y, h1, h2 = host
    stored = np.asarray(y).reshape(-1)[:n].reshape(lp.shape)
    meta = {"applied": True, "orig_dtype": "float32",
            "digest": ops.fold_digest(np.asarray(h1), np.asarray(h2), n=n),
            "digest_alg": ops.DIGEST_ALG, "encoder": "device"}
    return stored, meta


def encode_leaves(plan, source: dict, prev_host_tree: dict | None = None,
                  executor=None, *, depth: int = DEPTH,
                  interpret: bool = False) -> dict:
    """Start the device encode stage for a DumpPlan.

    source: {path: array} — device-resident when the caller has them
    (session.save passes the original tree), host arrays otherwise (the
    stage uploads; on CPU backends upload is free). Returns {path: Future
    -> (stored np.ndarray, codec_meta)} covering the eligible leaves; the
    executor's do_leaf falls through to the host codec for every other
    path. A failure is set on every future not yet landed; run inline,
    it is also raised from here.
    """
    prev_host_tree = prev_host_tree or {}
    todo = [lp for lp in plan.leaves
            if eligible(lp) and lp.path in source
            and (lp.codec != "delta8" or lp.path in prev_host_tree)]
    if not todo:
        return {}
    futs = {lp.path: Future() for lp in todo}

    def dispatch(lp):
        x = jnp.asarray(source[lp.path], jnp.float32).reshape(-1)
        if lp.codec == "delta8":
            prev = jnp.asarray(prev_host_tree[lp.path],
                               jnp.float32).reshape(-1)
            return ops.delta_encode_digest(x, prev, block=CODEC_BLOCK,
                                           interpret=interpret)
        return ops.bf16_encode_digest(x, block=CODEC_BLOCK,
                                      interpret=interpret)

    def land_oldest(pending):
        lp, out = pending.popleft()
        futs[lp.path].set_result(_land(lp, out))

    def pump():
        pending = deque()
        try:
            for lp in todo:
                pending.append((lp, dispatch(lp)))
                while len(pending) >= depth:
                    land_oldest(pending)
            while pending:
                land_oldest(pending)
        except BaseException as e:
            # a device failure fails the dump with the original error:
            # every leaf still waiting on the stage gets it, so no chunk
            # writer blocks on a result that will never land
            for fut in futs.values():
                if not fut.done():
                    fut.set_exception(e)
            raise

    started = executor.submit_cpu(pump) if executor is not None else None
    if started is None:
        pump()    # serial engine / no executor: inline, no overlap
    return futs
