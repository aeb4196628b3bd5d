"""Pallas TPU kernels for the platform's compute hot-spots.

Each kernel ships three files:
    <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
    ops.py    — jit'd public wrappers that dispatch kernel vs reference
    ref.py    — pure-jnp oracles the tests assert against

Kernels run in interpret mode on CPU (validation) and compiled on TPU,
except the emulator's two kernels (``hmmu_lookup``, ``chunk_step``): the
TPU compiler refuses both, so on a TPU the default path takes XLA's
native gather and the scan path instead (``ops.REFUSED_ON_TPU``).
Set ``REPRO_FORCE_PALLAS=1`` to force the kernel path (interpret on CPU;
on a TPU a refused kernel then raises the compiler's error),
``REPRO_FORCE_REF=1`` to force the reference path.
"""
from .ops import (flash_attention, decode_attention, hmmu_lookup,
                  rwkv_chunk, use_pallas)

__all__ = ["flash_attention", "decode_attention", "hmmu_lookup",
           "rwkv_chunk", "use_pallas"]
