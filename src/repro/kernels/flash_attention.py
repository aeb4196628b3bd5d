"""Blocked causal flash attention — Pallas TPU kernel.

Tiling: grid (batch*q_heads, q_blocks, kv_blocks), kv innermost with
'arbitrary' semantics so the online-softmax accumulators persist in VMEM
scratch across kv steps. Block shapes are MXU-aligned (multiples of 128 on
the matmul dims when the head dim allows). Causal and sliding-window block
skipping happens at grid level via @pl.when — skipped blocks cost a VMEM
tile load, not an MXU pass.

GQA is handled by the k/v index maps (q head h reads kv head h // group),
so kv tiles are fetched once per group from HBM's point of view after
XLA's revisit caching.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            window: int | None, seq_q: int, seq_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Absolute positions. q may be the tail of the kv sequence.
    q_off = seq_kv - seq_q
    qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_off
    ki = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # Block-level skip: entirely-masked kv blocks do no compute.
    blk_lo = ik * block_k                      # first ki in block
    q_hi = iq * block_q + block_q - 1 + q_off  # last qi in block
    needed = True
    if causal:
        needed = blk_lo <= q_hi
    if window is not None:
        q_lo = iq * block_q + q_off
        needed = needed & (ik * block_k + block_k - 1 >= q_lo - window + 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)       # [block_q, d]
        k = k_ref[0].astype(jnp.float32)       # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= qi - ki < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)        # fully-masked rows -> 0 output
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] -> [B, Hq, Sq, D]."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, block_q, skv, block_k)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)

    grid = (b * hq, sq // block_q, skv // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          seq_q=sq, seq_kv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, iq, ik, g=group: (h // g, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, iq, ik, g=group: (h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
