"""RWKV6 chunked linear attention — Pallas TPU kernel.

The chunked formulation (models/rwkv.py) turns the data-dependent-decay
recurrence into per-chunk matmuls plus a tiny cross-chunk state update.
This kernel keeps the [Dk, Dv] state in VMEM scratch across the chunk
grid axis ('arbitrary'), so HBM sees each token exactly once — the
recurrence never round-trips.

Grid: (B*H, S/C). Blocks: r/k/v/logw tiles [C, D] in VMEM; u row [1, D].
All accumulation fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *,
            chunk: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # [C, Dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # [C, Dv]
    lw = lw_ref[0].astype(jnp.float32)        # [C, Dk] log-decay (negative)
    u = u_ref[0].astype(jnp.float32)          # [Dk]

    lw_cum = jnp.cumsum(lw, axis=0)
    lw_tot = lw_cum[-1]                       # [Dk]

    qp = r * jnp.exp(lw_cum - lw)             # r_t * A_{t-1}
    kp = k * jnp.exp(-lw_cum)                 # k_s / A_s
    kt = k * jnp.exp(lw_tot[None, :] - lw_cum)

    att = jax.lax.dot_general(qp, kp, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    c = att.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    att = jnp.where(si < ti, att, 0.0)        # strictly lower triangular
    diag = jnp.sum(r * k * u[None, :], axis=1)

    intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    intra = intra + diag[:, None] * v
    carry = jax.lax.dot_general(qp, state_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = (intra + carry).astype(o_ref.dtype)

    state_ref[...] = state_ref[...] * jnp.exp(lw_tot)[:, None] + \
        jax.lax.dot_general(kt, v, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_chunk_scan(r, k, v, logw, u, chunk: int = 128,
                    interpret: bool = False):
    """r/k/v/logw: [B,H,S,D]; u: [H,D] -> out [B,H,S,Dv] (fp32).

    Returns the per-position outputs only (the final state, needed for
    decode hand-off, comes from the jnp reference path — training uses
    outputs alone)."""
    b, h, s, dk = r.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    assert s % c == 0, (s, c)

    flat = lambda x: x.reshape(b * h, s, x.shape[-1])
    u_flat = jnp.broadcast_to(u[None], (b, h, dk)).reshape(b * h, dk)

    grid = (b * h, s // c)
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, c, dk), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, c, dv), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, c, dk), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, dk), lambda i, n: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, dv), lambda i, n: (i, n, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(logw), u_flat)
    return out.reshape(b, h, s, dv)
