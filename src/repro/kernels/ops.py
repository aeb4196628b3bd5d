"""Public jit'd wrappers: dispatch Pallas kernel vs jnp reference.

Kernel path on TPU (compiled) or when REPRO_FORCE_PALLAS=1 (interpret mode
on CPU — used by the kernel test suite). Reference path everywhere else,
including the multi-pod dry-run on the CPU host, and on a TPU for the
kernels in :data:`REFUSED_ON_TPU` (see :func:`use_pallas`).

``flash_attention`` is differentiable: the Pallas forward pairs with a
recompute-based reference backward via jax.custom_vjp (the standard
memory-saving trade — the backward re-runs reference attention under
autodiff, which XLA fuses; a dedicated backward kernel is a possible
future optimization and would not change the roofline compute term).
"""
from __future__ import annotations

import functools
import os

import jax
from jax import custom_batching

from . import ref
from . import flash_attention as _fa
from . import decode_attention as _da
from . import hmmu_lookup as _hl
from . import rwkv_scan as _rw


# Kernels the TPU compiler refuses at the emulator's geometry. On a TPU
# the default path leaves them to XLA's native gather (``hmmu_lookup``)
# and the scan path (``chunk_step``); an explicit request
# (REPRO_FORCE_PALLAS=1, or ``chunk_step_kernel="on"``) still selects
# them, and the compile then raises the compiler's error.
#   hmmu_lookup — its (1, 1, 8) row block is below the (8, 128) int32 tile;
#   chunk_step  — vector loads from the scalar-prefetch (SMEM) operand, and
#                 the whole table as both an input and an output VMEM block.
REFUSED_ON_TPU = frozenset({"hmmu_lookup", "chunk_step"})


def use_pallas(kernel: str) -> bool:
    """Static, host-side dispatch: does ``kernel`` take its Pallas form?
    REPRO_FORCE_REF=1 says no and REPRO_FORCE_PALLAS=1 says yes (interpret
    mode off-TPU); otherwise yes on a TPU backend, unless ``kernel`` is in
    :data:`REFUSED_ON_TPU`. Keys on the process's default backend, not on
    where an array lives."""
    if os.environ.get("REPRO_FORCE_REF"):
        return False
    if os.environ.get("REPRO_FORCE_PALLAS"):
        return True
    return jax.default_backend() == "tpu" and kernel not in REFUSED_ON_TPU


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# flash attention (training / prefill)
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attn(q, k, v, causal, window, scale):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=_interpret())


def _flash_attn_fwd(q, k, v, causal, window, scale):
    out = _flash_attn(q, k, v, causal, window, scale)
    return out, (q, k, v)


def _flash_attn_bwd(causal, window, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.attention(q, k, v, causal=causal, window=window,
                                      scale=scale), q, k, v)
    return vjp(g)


_flash_attn.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None) -> jax.Array:
    """[B, Hq, Sq, D] x [B, Hkv, Skv, D]^2 -> [B, Hq, Sq, D]."""
    if use_pallas("flash_attention"):
        return _flash_attn(q, k, v, causal, window, scale)
    return ref.attention(q, k, v, causal=causal, window=window, scale=scale)


# --------------------------------------------------------------------------- #
# flash decode (serving)
# --------------------------------------------------------------------------- #

def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *, scale: float | None = None,
                     window: int | None = None) -> jax.Array:
    """[B, Hq, D] x [B, Hkv, Smax, D]^2 + int32[B] -> [B, Hq, D]."""
    if use_pallas("decode_attention"):
        return _da.decode_attention(q, k_cache, v_cache, kv_len, scale=scale,
                                    window=window, interpret=_interpret())
    return ref.decode_attention(q, k_cache, v_cache, kv_len, scale=scale,
                                window=window)


# --------------------------------------------------------------------------- #
# HMMU table lookup (emulation platform hot loop)
# --------------------------------------------------------------------------- #

@custom_batching.custom_vmap
def _hmmu_lookup_pallas(table: jax.Array, pages: jax.Array) -> jax.Array:
    return _hl.hmmu_lookup(table, pages, interpret=_interpret())


@_hmmu_lookup_pallas.def_vmap
def _hmmu_lookup_vmap(axis_size, in_batched, table, pages):
    # vmap (the sweep's design-point axis) becomes the kernel's leading
    # batch/grid axis: one launch gathers every design point's chunk. The
    # sweep batches the table (per-point state) but shares the trace, so
    # broadcast whichever operand isn't batched.
    table_b, pages_b = in_batched
    if not table_b:
        table = jax.numpy.broadcast_to(table, (axis_size, *table.shape))
    if not pages_b:
        pages = jax.numpy.broadcast_to(pages, (axis_size, *pages.shape))
    return _hmmu_lookup_pallas(table, pages), True


def hmmu_lookup(table: jax.Array, pages: jax.Array) -> jax.Array:
    """int32[*batch, n_pages, W] x int32[*batch, chunk]
    -> int32[*batch, chunk, W]. Page indices are clamped to the table
    extent in both paths (bounds safety)."""
    if use_pallas("hmmu_lookup"):
        return _hmmu_lookup_pallas(table, pages)
    return ref.hmmu_lookup(table, pages)


def hmmu_lookup_fused(table: jax.Array, pages: jax.Array,
                      extra: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused gather of a chunk's rows plus ``k`` extra rows (the emulator
    passes the DMA swap pair, so stage 2 needs exactly one launch per
    step). The extra indices are appended to the prefetch vector and the
    combined gather goes through the SAME batched kernel / custom_vmap
    rule as :func:`hmmu_lookup` — a vmapped sweep still fuses every
    design point into one launch. Returns (chunk rows, extra rows)."""
    if use_pallas("hmmu_lookup"):
        return ref.fused_gather(_hmmu_lookup_pallas, table, pages, extra)
    return ref.hmmu_lookup_fused(table, pages, extra)


# --------------------------------------------------------------------------- #
# rwkv6 chunked linear attention (SSM-family training hot spot)
# --------------------------------------------------------------------------- #

def rwkv_chunk(r, k, v, logw, u, *, chunk: int = 128):
    """[B,H,S,D]^4 + [H,D] -> fp32 [B,H,S,Dv]. Kernel on TPU, jnp
    reference elsewhere (the reference also returns the carry state used
    by decode; see models.rwkv)."""
    if use_pallas("rwkv_chunk"):
        return _rw.rwkv_chunk_scan(r, k, v, logw, u, chunk=chunk,
                                   interpret=_interpret())
    from repro.models.rwkv import rwkv_chunk_scan as _ref
    return _ref(r, k, v, logw, u, chunk)[0]
