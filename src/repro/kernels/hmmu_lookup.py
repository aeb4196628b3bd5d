"""HMMU redirection-table lookup engine — Pallas TPU kernel.

The paper's hottest pipeline stage: for every request in a chunk, fetch
the page's redirection-table row (device, frame, hotness, wear, owner,
epoch, flags — the packed layout defined in ``repro.core.table``).
On the FPGA this is a BRAM read per cycle; the TPU-native analogue is a
scalar-prefetch-driven DMA gather: the page indices ride in SMEM ahead of
the grid (``PrefetchScalarGridSpec``), and each grid step's BlockSpec
index_map *is* the table lookup — the DMA engine chases the indices
through HBM while compute overlaps.

The kernel is layout-agnostic (it gathers whole rows of whatever width
the table carries) and batched: a leading batch axis on ``table`` and
``pages`` maps to a leading grid axis, so a vmapped design-space sweep
(``repro.sweep``) gathers the rows of *every* design point's chunk in one
kernel launch. Page indices are clamped to the table extent before the
gather — an out-of-range page can never make the index_map fetch an
arbitrary row.

W=8 keeps rows compact, and that is why the TPU compiler refuses this
kernel: a (1, 1, 8) block is below the (8, 128) int32 tile, and the
chip tiles the table's 8-lane rows to 128 lanes in HBM, so a row DMA
out of an HBM-resident table is refused too. On a TPU the default path
therefore uses XLA's native gather (``kernels.ops.REFUSED_ON_TPU``);
this kernel runs in interpret mode off-TPU. A TPU form would view the
table as ``int32[n_pages * 8 / 128, 128]`` and select the row from the
fetched tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int32 lanes per table row. Must equal ``repro.core.table.ROW_W`` (the
# authoritative layout; kept separate to avoid a core <-> kernels import
# cycle — the test suite asserts the two agree).
ROW_W = 8


def _kernel(pages_ref, table_ref, out_ref):
    # pages_ref is the scalar-prefetch operand; the gather already happened
    # in the index_map. The body just moves the row VMEM -> VMEM.
    out_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hmmu_lookup(table: jax.Array, pages: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """Gather redirection-table rows for one or many request chunks.

    table: int32[*batch, n_pages, W]; pages: int32[*batch, chunk]
    -> int32[*batch, chunk, W]. ``batch`` may be empty (single platform)
    or any leading shape (e.g. the sweep's design-point axis); batch dims
    of ``table`` and ``pages`` must match. ``pages`` entries are clamped
    to [0, n_pages).
    """
    batch = table.shape[:-2]
    n_pages, w = table.shape[-2:]
    chunk = pages.shape[-1]
    if pages.shape[:-1] != batch:
        raise ValueError(
            f"batch dims disagree: table {batch} vs pages {pages.shape[:-1]}")
    # Bounds safety: an out-of-range page must not index whatever the
    # index_map would produce (mod-n wraparound on TPU, UB elsewhere).
    pages = jnp.clip(pages.astype(jnp.int32), 0, n_pages - 1)

    tb = table.reshape((-1, n_pages, w))
    pg = pages.reshape((-1, chunk))
    b = tb.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, chunk),
        in_specs=[
            pl.BlockSpec((1, 1, w), lambda bi, i, pages: (bi, pages[bi, i], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, w), lambda bi, i, pages: (bi, i, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, chunk, w), jnp.int32),
        interpret=interpret,
    )(pg, tb)
    return out.reshape(*batch, chunk, w)


def hmmu_lookup_fused(table: jax.Array, pages: jax.Array,
                      extra: jax.Array, *, interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """Gather a chunk's rows AND a few extra rows (the DMA swap pair) in
    ONE kernel launch: the extra page indices ride at the tail of the
    scalar-prefetch vector, extending the grid to ``chunk + k`` steps.

    table: int32[*batch, n_pages, W]; pages: int32[*batch, chunk];
    extra: int32[*batch, k] -> (int32[*batch, chunk, W],
    int32[*batch, k, W]). Same clamp semantics as :func:`hmmu_lookup`.
    """
    from .ref import fused_gather
    return fused_gather(functools.partial(hmmu_lookup, interpret=interpret),
                        table, pages, extra)
