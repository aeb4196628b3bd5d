"""Flash-decode — single-token attention over a long KV cache, Pallas TPU.

The decode hot spot is memory-bound: one query row must stream the whole
cache from HBM. The kernel tiles the cache on the sequence axis (grid
(batch*q_heads, kv_blocks)) and keeps the running (max, sum, acc) partial
softmax in VMEM scratch, so the cache is read exactly once at full HBM
bandwidth — the roofline optimum for decode. Valid-length masking handles
ragged batches; an optional sliding window serves the local layers of
window-attention architectures.

This kernel is what the tiered (DRAM/NVM-style) KV cache of repro.memtier
feeds: hot pages gathered into the contiguous fast-tier buffer are exactly
the ``k_cache``/``v_cache`` arguments here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_k: int, window: int | None, hq: int):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0]
    lo = ik * block_k
    needed = lo < kv_len
    if window is not None:
        needed = needed & (lo + block_k > kv_len - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [1, d] row
        k = k_ref[0].astype(jnp.float32)            # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)[0] * scale
        ki = lo + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
        mask = ki < kv_len
        if window is not None:
            mask &= ki >= kv_len - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p[None, :], v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[0]
        m_ref[0] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.where(l_ref[0] == 0.0, 1.0, l_ref[0])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *, scale: float | None = None,
                     window: int | None = None, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; k_cache/v_cache: [B, Hkv, Smax, D]; kv_len: int32[B]
    -> [B, Hq, D]."""
    b, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_k = min(block_k, smax)
    assert smax % block_k == 0, (smax, block_k)

    qr = q.reshape(b * hq, 1, d)
    kr = k_cache.reshape(b * hkv, smax, d)
    vr = v_cache.reshape(b * hkv, smax, d)
    lens = kv_len.astype(jnp.int32)

    grid = (b * hq, smax // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k,
                          window=window, hq=hq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda h, ik, hq=hq: (h // hq,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda h, ik: (h, 0, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, ik, g=group: (h // g, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, ik, g=group: (h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda h, ik: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(b, hq, d)
