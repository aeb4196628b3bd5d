"""DMA page-migration engine with swap-progress conflict redirection
(paper §III-D).

The engine swaps two pages (one per device) in 512 B sub-blocks through an
internal staging buffer, tracking exactly which sub-blocks have already
been exchanged. A request that hits a page mid-swap is redirected by the
progress indicator: if its sub-block has already been transferred, the
request goes to the *destination* location; otherwise to the source. This
is the logic the paper reports spending "considerable time to design and
verify" — reproduced here and verified by property tests
(tests/test_dma.py).

One swap is in flight at a time (a single engine, as in the paper);
additional migration requests wait for the engine.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import table as table_lib
from .config import SLOW, EmulatorConfig, RuntimeParams


class DMAState(NamedTuple):
    active: jax.Array    # int32 {0,1}
    page_a: jax.Array    # int32 — page being demoted/first swap member
    page_b: jax.Array    # int32 — page being promoted/second swap member
    start: jax.Array     # int32 cycle at which the swap began
    swaps_done: jax.Array  # int32 counter — completed migrations

    @staticmethod
    def idle() -> "DMAState":
        z = jnp.int32(0)
        return DMAState(active=z, page_a=jnp.int32(-1), page_b=jnp.int32(-1),
                        start=z, swaps_done=z)


def exchange_cycles_per_subblock(cfg: EmulatorConfig,
                                 params: RuntimeParams | None = None):
    """Cycles to exchange one sub-block (A->buffer, B->A, buffer->B).
    Returns a python int from ``cfg`` alone (host-side simulators), or a
    traced int32 when ``params`` carries the DMA bandwidth."""
    eff = cfg if params is None else params
    return 3 * eff.dma_cycles_per_subblock


def swap_duration(cfg: EmulatorConfig, params: RuntimeParams | None = None):
    return cfg.subblocks_per_page * exchange_cycles_per_subblock(cfg, params)


def progress_subblocks(cfg: EmulatorConfig, dma: DMAState, t: jax.Array,
                       params: RuntimeParams | None = None) -> jax.Array:
    """Number of fully exchanged sub-blocks at time ``t`` (int32, clamped)."""
    raw = (t - dma.start) // exchange_cycles_per_subblock(cfg, params)
    raw = jnp.where(dma.active == 1, raw, 0)
    return jnp.clip(raw, 0, cfg.subblocks_per_page)


def redirect(cfg: EmulatorConfig, dma: DMAState,
             page: jax.Array, offset: jax.Array, t: jax.Array,
             device: jax.Array, frame: jax.Array,
             row_a: jax.Array, row_b: jax.Array,
             params: RuntimeParams | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Apply swap-progress redirection to a chunk of requests.

    page/offset/t/device/frame: int32[chunk] — request fields and the
    *pre-swap* table lookup results. ``row_a``/``row_b`` are the packed
    table rows (pre-swap) of the in-flight swap pair.

    Returns (device, frame) actually accessed by each request.
    """
    prog = progress_subblocks(cfg, dma, t, params)    # int32[chunk]
    blk = offset // cfg.subblock
    transferred = blk < prog                           # sub-block already moved

    hit_a = (dma.active == 1) & (page == dma.page_a)
    hit_b = (dma.active == 1) & (page == dma.page_b)

    # Transferred sub-blocks live at the counterpart's (pre-swap) location.
    device = jnp.where(hit_a & transferred, table_lib.device(row_b), device)
    frame = jnp.where(hit_a & transferred, table_lib.frame(row_b), frame)
    device = jnp.where(hit_b & transferred, table_lib.device(row_a), device)
    frame = jnp.where(hit_b & transferred, table_lib.frame(row_a), frame)
    return device, frame


class SwapCommit(NamedTuple):
    """A swap commit expressed as pure data: the new engine state plus the
    table writes as (row, lane, int32-delta) scatter-add triples computed
    from *prefetched* pre-chunk rows. The emulator folds these triples into
    its single combined boundary scatter (one in-place update per chunk);
    :func:`maybe_complete` applies them directly for standalone callers.
    Every write is an exact int32 delta against the prefetched value, so
    add-commit is bitwise identical to the historical set-commit."""
    dma: DMAState
    done: jax.Array    # bool — swap finished this boundary
    rows: jax.Array    # int32[10] target rows (idle/no-op entries hit row 0
    #   with delta 0 — the guard-index convention of the old set path)
    lanes: jax.Array   # int32[10] target lanes, aligned with ``rows``
    delta: jax.Array   # int32[10] value to add at (row, lane)
    tombstone: jax.Array  # int32 — page parked on a dead frame by this
    #   commit (POISONED|RETIRED stamped via the FLAGS deltas), else -1
    rescued: jax.Array    # int32 — page whose pending rescue this commit
    #   completed (POISONED cleared), else -1


def plan_commit(cfg: EmulatorConfig, dma: DMAState, now: jax.Array,
                row_a: jax.Array, row_b: jax.Array,
                params: RuntimeParams | None = None,
                rescue_page=None) -> SwapCommit:
    """Plan the chunk-boundary swap commit from prefetched rows.

    ``row_a``/``row_b`` are the packed *pre-chunk* table rows of the swap
    pair (guard-indexed: row 0 when idle) — the same rows stage 2's fused
    gather already fetched, so the commit needs NO table reads of its own.
    Valid because nothing earlier in a chunk writes the DEVICE/FRAME/EPOCH
    lanes these deltas are computed against (the read-before-write chunk
    schedule; see kernels.chunk_step).

    If the in-flight swap has finished by ``now``: exchange the two pages'
    DEVICE and FRAME lanes, stamp their EPOCH lane with the commit cycle,
    and charge the migration's full-page write to the WEAR lane of
    whichever slow frame received data (endurance accounting for the swap
    traffic itself, in line-sized units comparable to demand writes).

    Poison travel (retirement rescues): POISONED marks "the frame under
    this page is dead", so when a swap involving a poisoned member
    commits, the poison stays with the *frame*: the counterpart page —
    which now sits on the dead frame — becomes a ``POISONED|RETIRED``
    tombstone (pins force-cleared; the serving layer renegotiates), and
    the formerly poisoned member comes out clean on the healthy frame.
    This one rule covers both scheduled rescue migrations and the
    adversarial corner where a frame dies while its page is already a
    swap endpoint.

    ``rescue_page`` is the emulator's rescue register
    (``EmulatorState.rescue_page``): poison only travels for the page the
    retirement subsystem actually marked dying (the register holds at
    most one). POISONED set by anything else — tests poison pages purely
    for the observability counter — commits exactly as before, and with
    ``rescue_page`` absent (None / -1, the default and every legacy
    caller) every FLAGS delta is zero, so the commit stays
    bitwise-identical to the pre-retirement engine.
    """
    done = (dma.active == 1) & (now >= dma.start + swap_duration(cfg, params))

    a, b = dma.page_a, dma.page_b
    # `a`/`b` are -1 when idle; guard indices target row 0 with delta 0.
    ia = jnp.where(a >= 0, a, 0)
    ib = jnp.where(b >= 0, b, 0)
    da, db = table_lib.device(row_a), table_lib.device(row_b)
    fa, fb = table_lib.frame(row_a), table_lib.frame(row_b)
    ea, eb = table_lib.epoch(row_a), table_lib.epoch(row_b)
    commit_a = done & (a >= 0)
    commit_b = done & (b >= 0)

    # WEAR charge: the DMA wrote one whole page into each destination; only
    # the slow-tier destination has limited endurance. Post-commit, member
    # `a` sits on device `db` at frame `fb` (and vice versa) — charge the
    # member that landed on SLOW.
    charge = cfg.page_size // cfg.line_size
    chg_a = commit_a & (db == SLOW)   # a demoted into slow frame fb
    chg_b = commit_b & (da == SLOW)   # b demoted into slow frame fa

    # Constants stay Python literals (not eager jnp arrays): this function
    # also traces inside the one-kernel Pallas body, which rejects
    # captured device constants. The DEVICE/DEVICE/FRAME/FRAME/EPOCH/
    # EPOCH/WEAR/WEAR lane vector is built from an iota for the same
    # reason.
    # Poison travel (see docstring): new FLAGS as pure int32 deltas against
    # the prefetched pre-chunk values. Bit constants are Python literals
    # for the same Pallas reason as above.
    rp = -1 if rescue_page is None else rescue_page
    fla, flb = table_lib.flags(row_a), table_lib.flags(row_b)
    dead_a = ((fla & table_lib.POISONED) != 0) & (a == rp) & (a >= 0)
    dead_b = ((flb & table_lib.POISONED) != 0) & (b == rp) & (b >= 0)
    dead_bits = table_lib.POISONED | table_lib.RETIRED
    new_fla = jnp.where(dead_b, (fla | dead_bits) & ~table_lib.PINNED,
                        jnp.where(dead_a, fla & ~dead_bits, fla))
    new_flb = jnp.where(dead_a, (flb | dead_bits) & ~table_lib.PINNED,
                        jnp.where(dead_b, flb & ~dead_bits, flb))

    rows = jnp.stack([ia, ib, ia, ib, ia, ib,
                      jnp.where(chg_a, fb, 0), jnp.where(chg_b, fa, 0),
                      ia, ib])
    k = jnp.repeat(jnp.arange(5, dtype=jnp.int32), 2)
    lanes = table_lib.swap_commit_lanes(k)
    delta = jnp.stack([jnp.where(commit_a, db - da, 0),
                       jnp.where(commit_b, da - db, 0),
                       jnp.where(commit_a, fb - fa, 0),
                       jnp.where(commit_b, fa - fb, 0),
                       jnp.where(commit_a, now - ea, 0),
                       jnp.where(commit_b, now - eb, 0),
                       jnp.where(chg_a, charge, 0),
                       jnp.where(chg_b, charge, 0),
                       jnp.where(commit_a, new_fla - fla, 0),
                       jnp.where(commit_b, new_flb - flb, 0)])

    any_dead = (commit_a & dead_a) | (commit_b & dead_b)
    tombstone = jnp.where(any_dead, jnp.where(dead_a, b, a), -1)
    rescued = jnp.where(any_dead, jnp.where(dead_a, a, b), -1)

    new = DMAState(
        active=jnp.where(done, 0, dma.active).astype(jnp.int32),
        page_a=jnp.where(done, -1, dma.page_a).astype(jnp.int32),
        page_b=jnp.where(done, -1, dma.page_b).astype(jnp.int32),
        start=dma.start,
        swaps_done=dma.swaps_done + done.astype(jnp.int32),
    )
    return SwapCommit(dma=new, done=done, rows=rows, lanes=lanes,
                      delta=delta, tombstone=jnp.int32(tombstone),
                      rescued=jnp.int32(rescued))


def maybe_complete(cfg: EmulatorConfig, dma: DMAState, now: jax.Array,
                   table: jax.Array, params: RuntimeParams | None = None,
                   rescue_page=None
                   ) -> tuple["DMAState", jax.Array, jax.Array]:
    """At a chunk boundary: commit the in-flight swap if it has finished
    by ``now`` (see :func:`plan_commit` for the semantics). Standalone
    entry point over :func:`plan_commit` that gathers the swap pair's rows
    itself and applies the planned deltas to ``table``.
    Returns (state, table, done_flag)."""
    ia = jnp.maximum(dma.page_a, 0)
    ib = jnp.maximum(dma.page_b, 0)
    plan = plan_commit(cfg, dma, now, table_lib.rows(table, ia),
                       table_lib.rows(table, ib), params, rescue_page)
    # WEAR deltas saturate at WEAR_CAP like the chunk-boundary commit
    # (at most one WEAR charge per commit — a swap always pairs FAST with
    # SLOW — so a plain min against the headroom is exact).
    pre = table_lib.lane_at(table, plan.rows, plan.lanes)
    delta = jnp.where(
        plan.lanes == table_lib.WEAR,
        jnp.minimum(plan.delta,
                    jnp.maximum(jnp.int32(table_lib.WEAR_CAP) - pre, 0)),
        plan.delta)
    table = table_lib.add_at(table, plan.rows, plan.lanes, delta)
    return plan.dma, table, plan.done


def maybe_start(dma: DMAState, want: jax.Array, page_a: jax.Array,
                page_b: jax.Array, now: jax.Array,
                table: jax.Array | None = None
                ) -> tuple[DMAState, jax.Array]:
    """Start a new swap if the engine is idle, the policy wants one, and
    neither swap member is pinned or a retirement tombstone (when
    ``table`` is given, its FLAGS lane is the engine's own guard —
    defense in depth below the emulator's post-policy mask, so
    user-registered policies cannot migrate pinned pages or exhume dead
    frames either; a merely POISONED member is allowed — that is how
    rescue migrations move a page off its dead frame). Returns
    ``(state, started)``; callers thread ``started`` back into the CLOCK
    pointer commit, so a dropped proposal (engine busy, pinned member,
    re-masked want) never advances the pointer past an unconsumed victim
    frame."""
    if table is not None:
        veto_bits = table_lib.PINNED | table_lib.RETIRED
        vetoed = ((table_lib.flags_at(table, page_a) |
                   table_lib.flags_at(table, page_b)) & veto_bits) != 0
        want = want & ~vetoed
    start_it = (dma.active == 0) & want
    return DMAState(
        active=jnp.where(start_it, 1, dma.active).astype(jnp.int32),
        page_a=jnp.where(start_it, page_a, dma.page_a).astype(jnp.int32),
        page_b=jnp.where(start_it, page_b, dma.page_b).astype(jnp.int32),
        start=jnp.where(start_it, now, dma.start).astype(jnp.int32),
        swaps_done=dma.swaps_done,
    ), start_it
