"""Pallas TPU flash attention (forward): online softmax over KV blocks with
explicit BlockSpec VMEM tiling.

TPU adaptation of the FlashAttention insight (HBM->SRAM tiling on GPU):
blocks are shaped for the MXU (q_block x head_dim and head_dim x k_block
matmuls with 128-aligned dims), the kv axis is the innermost ("arbitrary")
grid dimension so the running (m, l, acc) state lives in VMEM scratch across
kv steps, and causal/window block-skipping is done with pl.when on block
coordinates. GQA is handled by indexing the KV head via the BlockSpec index
map (no materialized repeat).

Validated in interpret mode on CPU against ref.py across shape/dtype sweeps
(tests/test_kernels_flash.py); on TPU fleets this is the serving/prefill
attention path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
_MFLOOR = -1e9


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            blk_q: int, blk_k: int, n_kv: int, q_off: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: no (q, k) pair in this tile can be unmasked
    q_lo = q_off + qi * blk_q                 # first q position in tile
    q_hi = q_lo + blk_q - 1
    k_lo = ki * blk_k
    k_hi = k_lo + blk_k - 1
    live = jnp.asarray(True)
    if causal:
        live &= q_hi >= k_lo
    if window:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale              # [blk_q, hd]
        k = k_ref[0].astype(jnp.float32)                      # [blk_k, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, bool)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        m_safe = jnp.maximum(m_new, _MFLOOR)
        p = jnp.exp(s - m_safe[:, None])
        corr = jnp.exp(jnp.maximum(m_prev, _MFLOOR) - m_safe)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, softcap=0.0,
                        blk_q=128, blk_k=128, interpret=False):
    """q [B,Sq,H,hd]; k,v [B,Skv,KV,hd] (KV divides H). q occupies the last
    Sq slots of the kv stream (q_off = Skv-Sq). Returns [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    assert Sq % blk_q == 0 and Skv % blk_k == 0, (Sq, blk_q, Skv, blk_k)
    n_q, n_kv = Sq // blk_q, Skv // blk_k
    q_off = Skv - Sq

    # fold heads into the leading grid dim: q [B*H, Sq, hd], kv [B*KV, Skv, hd]
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * KV, Skv, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * KV, Skv, hd)

    from jax.experimental.pallas import tpu as pltpu
    kern = functools.partial(
        _kernel, scale=hd ** -0.5, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, n_kv=n_kv, q_off=q_off)
    out = pl.pallas_call(
        kern,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
            pl.BlockSpec((1, blk_k, hd),
                         lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q,), jnp.float32),
                        pltpu.VMEM((blk_q,), jnp.float32),
                        pltpu.VMEM((blk_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(B, H, Sq, hd), 1, 2)
