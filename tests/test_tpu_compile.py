"""The codec kernels compile for a TPU v5e at real leaf sizes.

Nothing runs here: each kernel is lowered and compiled for a described
(not attached) v5e, which is where the TPU compiler refuses block shapes
that interpret mode accepts. The sizes come from the qwen3-8b training
state at published widths: the `wk` projection (4096 x 1024), the
18992 x 4096 embedding, and one ragged length that is not a multiple of
the codec block.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ckpt_codec import ckpt_codec as K
from repro.kernels.ckpt_codec.ops import BLOCK

LEAF_SIZES = {"wk": 4096 * 1024, "embed": 18992 * 4096, "ragged": 1000 * 1001}

KERNELS = {
    "delta_encode": lambda x, q, s, w: (K.delta_encode_pallas, (x, x)),
    "delta_encode_digest": lambda x, q, s, w: (
        K.delta_encode_digest_pallas, (x, x, w)),
    "bf16_encode_digest": lambda x, q, s, w: (
        K.bf16_encode_digest_pallas, (x, w)),
    "digest_blocks": lambda x, q, s, w: (K.digest_blocks_pallas, (x, w)),
    "delta_decode": lambda x, q, s, w: (K.delta_decode_pallas, (q, s, x)),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("leaf", sorted(LEAF_SIZES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_codec_kernel_compiles_for_v5e(one_chip, kernel, leaf):
    nblk = -(-LEAF_SIZES[leaf] // BLOCK)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    fn, args = KERNELS[kernel](shape((nblk, BLOCK), jnp.float32),
                               shape((nblk, BLOCK), jnp.int8),
                               shape((nblk,), jnp.float32),
                               shape((2, BLOCK), jnp.uint32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
