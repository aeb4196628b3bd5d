"""Packed redirection-table store + allocator middleware.

Heterogeneity transparency (paper §III-B): the OS/application sees one flat
physical space; the HMMU translates physical page -> (device, frame). The
mapping *is* the placement policy's state and migrations rewrite it.

All per-page metadata lives in ONE packed ``int32[n_pages, ROW_W]`` array
whose row layout is shared verbatim with the Pallas lookup engine
(``repro.kernels.hmmu_lookup``) — on the FPGA this is the BRAM word the
redirection table serves per cycle. Lanes (columns) of row ``i``:

    ======= ===========================================================
    lane    meaning
    ======= ===========================================================
    DEVICE  tier of page ``i`` (FAST=0 / SLOW=1)
    FRAME   frame of page ``i`` within its device
    HOTNESS aging access counter of page ``i`` (policy state)
    WEAR    writes absorbed by *slow frame* ``i`` (endurance histogram)
    OWNER   inverse map: page owning *fast frame* ``i`` (CLOCK victims)
    EPOCH   cycle at which row ``i``'s mapping last changed (0 = never)
    FLAGS   protection bitfield: PIN_FAST / PIN_SLOW / POISONED
    ======= ===========================================================

FLAGS bits (the paper's §III-G placement hints, hardened into the table):

    ``PIN_FAST``  page is nailed to the fast tier — never a migration
                  candidate nor a CLOCK victim (hinted DRAM allocations);
    ``PIN_SLOW``  page is nailed to the slow tier — never promoted
                  (bulk/streaming allocations the hint keeps out of DRAM);
    ``POISONED``  the frame under this page is dead (its WEAR crossed
                  ``endurance_budget``, or a ``FaultPlan`` death fired) —
                  accesses still complete but raise ``poison_faults`` and
                  a rescue migration to a healthy frame is pending;
    ``RETIRED``   permanent tombstone: the page is parked on a dead frame
                  to keep it out of service (always POISONED too). It is
                  never a migration candidate, CLOCK victim or rescue
                  target — the frame is permanently out of circulation.

Retirement lifecycle: a frame death stamps POISONED on the resident page
(pins force-cleared — the hardware broke the contract; serving
renegotiates) and schedules a rescue swap with a healthy donor. When the
swap commits, the rescued page clears POISONED and the donor — now
sitting on the dead frame — becomes the ``POISONED|RETIRED`` tombstone.

Pin bits are enforced twice on the hot path (the emulator's post-policy
proposal mask AND ``dma.maybe_start``), so no policy — including
user-registered ones — can migrate a pinned page; the same double
enforcement keeps poisoned pages out of policy proposals and tombstones
out of every swap, so a pinned page can never land on a poisoned frame.

DEVICE/FRAME/HOTNESS/EPOCH/FLAGS are keyed by page number; WEAR and OWNER
reuse the same rows keyed by frame number (frames < n_pages always).
Policies, the DMA engine and the counters read named lanes through the
accessors below — never raw column indices.

Two forms of one table. ``EmulatorState.table`` is always the packed
``int32[n_pages, ROW_W]`` form above. For a table of
``emulator.FLAT_CARRY_MIN_BYTES`` or more, the emulator's chunk scan
carries the *flat lane-major* form ``int32[ROW_W * n_pages]`` instead —
element (page, lane) at ``lane * n_pages + page`` — converted once per
call on each side of the scan (:func:`to_flat` / :func:`to_packed`).
That is the form XLA's TPU scatter works on, so the boundary commit's
scatter-add no longer relays out the whole table around itself on every
chunk; a whole lane is one contiguous slice. This module is the only
one that knows either layout: the table-level accessors (:func:`n_pages`,
:func:`rows`, :func:`lane_at`, :func:`column`, :func:`add_at`,
:func:`set_at`, :func:`set_column`) take either form and decide by its
rank, and return the same values for both. The row accessors
(:func:`device`, :func:`frame`, :func:`flags`, :func:`is_pinned`, …)
read gathered rows ``[..., ROW_W]`` (and whole packed tables).

The paper's middleware (mem_driver.ko + modified jemalloc, §III-G) becomes
``HybridAllocator``: a host-side page allocator over the flat space that
honours placement *hints* (the paper's extended malloc API) by choosing
pages whose initial mapping lands on the preferred device. The serving
stack (repro.memtier) allocates KV-cache pages through this API.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import EmulatorConfig, FAST, SLOW

# Row layout. ``ROW_W`` is the row width the lookup kernel gathers; it must
# match ``repro.kernels.hmmu_lookup.ROW_W`` (asserted by the test suite —
# the kernel itself is layout-agnostic and reads the width off the array).
ROW_W = 8
DEVICE, FRAME, HOTNESS, WEAR, OWNER, EPOCH, FLAGS = range(7)
_PAD = 7  # spare lane keeping the row a power-of-two width

LANES = ("device", "frame", "hotness", "wear", "owner", "epoch", "flags")

# FLAGS-lane bits. PINNED is the "cannot migrate" test mask: either pin
# bit freezes the page's mapping (they differ only in which tier the page
# is nailed to, validated by check_table).
PIN_FAST = 1 << 0
PIN_SLOW = 1 << 1
POISONED = 1 << 2
RETIRED = 1 << 3
PINNED = PIN_FAST | PIN_SLOW
KNOWN_FLAGS = PIN_FAST | PIN_SLOW | POISONED | RETIRED

# Accumulator-lane saturation caps. HOTNESS and WEAR are monotone
# scatter-add counters fed every chunk; on runs long enough to matter
# (the paper's whole point) an uncapped int32 eventually wraps and
# silently corrupts the placement/retirement decision it drives. Both
# lanes saturate at this cap instead: far above any decision threshold
# (endurance budgets are < 2^27; hot_threshold is single digits) yet
# leaving > 2 bits of headroom below int32 overflow, so even a full
# chunk of duplicate weights added to a saturated lane cannot wrap.
# ``check_table`` (runtime) and ``repro.analysis.ranges`` (static)
# enforce the same invariant from these two constants.
HOTNESS_CAP = 1 << 29
WEAR_CAP = 1 << 29


class TableRows(NamedTuple):
    """Unpacked view of table rows — one array per named lane."""
    device: jax.Array
    frame: jax.Array
    hotness: jax.Array
    wear: jax.Array
    owner: jax.Array
    epoch: jax.Array
    flags: jax.Array


def device(table: jax.Array) -> jax.Array:
    """Tier of each page (FAST/SLOW). Works on [..., n, ROW_W] and on
    single rows [..., ROW_W]."""
    return table[..., DEVICE]


def frame(table: jax.Array) -> jax.Array:
    return table[..., FRAME]


def hotness(table: jax.Array) -> jax.Array:
    return table[..., HOTNESS]


def wear(table: jax.Array) -> jax.Array:
    return table[..., WEAR]


def owner(table: jax.Array) -> jax.Array:
    return table[..., OWNER]


def epoch(table: jax.Array) -> jax.Array:
    return table[..., EPOCH]


def flags(table: jax.Array) -> jax.Array:
    return table[..., FLAGS]


def is_pinned(table: jax.Array) -> jax.Array:
    """True where either pin bit is set. Works on full tables and on
    gathered rows ([..., ROW_W])."""
    return (table[..., FLAGS] & PINNED) != 0


def is_poisoned(table: jax.Array) -> jax.Array:
    return (table[..., FLAGS] & POISONED) != 0


def is_retired(table: jax.Array) -> jax.Array:
    """True where the page is a permanent tombstone on a dead frame."""
    return (table[..., FLAGS] & RETIRED) != 0


def is_flat(table: jax.Array) -> bool:
    """True for the flat lane-major form the chunk scan carries (rank 1),
    False for the packed ``[n_pages, ROW_W]`` form (rank 2)."""
    return table.ndim == 1


def n_pages(table: jax.Array) -> int:
    """Rows (pages) in a table of either form."""
    return table.shape[0] // ROW_W if is_flat(table) else table.shape[0]


def to_flat(table: jax.Array) -> jax.Array:
    """Packed ``[n_pages, ROW_W]`` -> flat lane-major ``[ROW_W * n_pages]``
    (lane ``k`` is the contiguous slice ``[k * n_pages, (k+1) * n_pages)``).
    The index stays int32: 8 x 41,943,040 pages is still below 2**31."""
    return table.T.reshape(-1)


def to_packed(table: jax.Array) -> jax.Array:
    """Inverse of :func:`to_flat`."""
    return table.reshape(ROW_W, -1).T


def _gather_index(table: jax.Array, idx) -> jax.Array:
    """Row indices under jnp's gather rule (negative indices count from
    the end, then clamp to the table), so both forms read the same rows
    for any index."""
    n = n_pages(table)
    idx = jnp.asarray(idx, jnp.int32)
    return jax.lax.clamp(0, jax.lax.select(idx < 0, idx + n, idx), n - 1)


def _scatter_index(table: jax.Array, idx, lane) -> jax.Array:
    """Flat indices of (row, lane) write targets. A row outside
    ``[0, n_pages)`` (after jnp's negative-index rule) — the commit's
    "no write" sentinel row ``n_pages`` above all — maps to ``ROW_W *
    n_pages``, past the end, so ``mode="drop"`` drops it. Left as
    ``lane * n_pages + row`` it would land on row 0 of the next lane."""
    n = n_pages(table)
    idx = jnp.asarray(idx, jnp.int32)
    idx = jax.lax.select(idx < 0, idx + n, idx)
    flat = jnp.asarray(lane, jnp.int32) * n + idx
    inside = jnp.broadcast_to((idx >= 0) & (idx < n), flat.shape)
    return jax.lax.select(inside, flat, jnp.full_like(flat, ROW_W * n))


def rows(table: jax.Array, idx) -> jax.Array:
    """Whole rows ``[..., ROW_W]`` at row indices ``idx`` (jnp's gather
    rule: negative from the end, then clamped). On the flat form, one
    gather over an index array ``lane * n_pages + idx[..., None]``."""
    if not is_flat(table):
        return table[idx]
    lanes = jnp.arange(ROW_W, dtype=jnp.int32) * n_pages(table)
    return table[lanes + _gather_index(table, idx)[..., None]]


def lane_at(table: jax.Array, idx, lane) -> jax.Array:
    """One lane of rows ``idx`` (``lane`` a constant or an array aligned
    with ``idx``) as a single-lane gather, no full-row fetch."""
    if not is_flat(table):
        return table[idx, lane]
    return table[jnp.asarray(lane, jnp.int32) * n_pages(table)
                 + _gather_index(table, idx)]


def column(table: jax.Array, lane: int) -> jax.Array:
    """The whole ``lane`` (a constant) as ``int32[n_pages]``: a contiguous
    slice of the flat form."""
    if not is_flat(table):
        return table[:, lane]
    n = n_pages(table)
    return jax.lax.slice(table, (lane * n,), ((lane + 1) * n,))


def set_column(table: jax.Array, lane: int, values) -> jax.Array:
    """Replace the whole ``lane`` with ``values`` (one in-place update of
    a contiguous slice on the flat form)."""
    if not is_flat(table):
        return table.at[:, lane].set(values)
    return jax.lax.dynamic_update_slice(
        table, jnp.asarray(values, table.dtype), (lane * n_pages(table),))


def add_at(table: jax.Array, idx, lane, upd) -> jax.Array:
    """Scatter-add ``upd`` at (row, lane) targets — ONE scatter. Rows
    outside the table drop (the sentinel-row convention)."""
    if not is_flat(table):
        return table.at[idx, lane].add(upd, mode="drop")
    return table.at[_scatter_index(table, idx, lane)].add(upd, mode="drop")


def set_at(table: jax.Array, idx, lane, values) -> jax.Array:
    """Store ``values`` at (row, lane) targets; rows outside the table
    drop."""
    if not is_flat(table):
        return table.at[idx, lane].set(values, mode="drop")
    return table.at[_scatter_index(table, idx, lane)].set(values,
                                                          mode="drop")


def device_at(table: jax.Array, pages) -> jax.Array:
    """DEVICE lane of ``pages`` as a single-lane gather (no full-row
    fetch) — the read the stamp/veto paths use."""
    return lane_at(table, pages, DEVICE)


def hotness_at(table: jax.Array, pages) -> jax.Array:
    """HOTNESS lane of ``pages`` as a single-lane gather."""
    return lane_at(table, pages, HOTNESS)


def wear_at(table: jax.Array, frames) -> jax.Array:
    """WEAR lane of ``frames`` (WEAR is keyed by slow frame) as a
    single-lane gather."""
    return lane_at(table, frames, WEAR)


def flags_at(table: jax.Array, pages) -> jax.Array:
    """FLAGS lane of ``pages`` as a single-lane gather."""
    return lane_at(table, pages, FLAGS)


def add_hotness(table: jax.Array, pages, w) -> jax.Array:
    """Scatter-add access weights into the HOTNESS lane (out-of-range
    pages drop — the sentinel-index convention of the boundary commit)."""
    return add_at(table, pages, HOTNESS, w)


def saturating_weights(targets: jax.Array, weights: jax.Array,
                       pre: jax.Array, cap: int) -> jax.Array:
    """Clip scatter-add ``weights`` so the accumulator lane at each
    target saturates at ``cap`` instead of wrapping ("fill until full").

    ``pre`` holds the pre-commit lane value gathered at ``targets``.
    Duplicate targets are exact: element ``i`` may add at most what is
    left of ``cap`` after the pre-value and every *earlier* element
    aimed at the same slot, so the scatter-add total per slot is
    ``min(sum(w), max(0, cap - pre))`` — order-independent, and the
    identity whenever the slot stays below the cap (existing golden
    digests are untouched). O(n^2) in the chunk width via a masked
    matrix, which is trivial next to the bank resolver.

    Written as explicit ``minimum(maximum(...))`` over a literal cap so
    the ``ranges`` static pass can recognise the saturation idiom in the
    jaxpr and certify the lane's int32 bound.
    """
    w = jnp.asarray(weights, jnp.int32)
    n = w.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    same_earlier = (targets[None, :] == targets[:, None]) & (i[None, :] <
                                                             i[:, None])
    psum = jnp.sum(jnp.where(same_earlier, w[None, :], 0), axis=1)
    allow = jnp.int32(cap) - pre - psum
    return jnp.minimum(jnp.maximum(allow, 0), w)


def decay_hotness(table: jax.Array, shift) -> jax.Array:
    """The aging tick: arithmetic-shift every page's HOTNESS lane (one
    lane update, in place on the flat form)."""
    return set_column(table, HOTNESS, column(table, HOTNESS) >> shift)


def store_flags(table: jax.Array, idx, values) -> jax.Array:
    """Store precomputed FLAGS values at rows ``idx`` (out-of-range
    sentinel rows drop). The traced counterpart of
    :func:`set_flags`/:func:`clear_flags` for batched stamp programs that
    compute the new FLAGS words themselves."""
    return set_at(table, idx, FLAGS, values)


def swap_commit_lanes(k: jax.Array) -> jax.Array:
    """Lane ids of the DMA swap commit's delta pairs, by pair index
    ``k``: (DEVICE, FRAME, EPOCH, WEAR, FLAGS) — the one place outside
    this module's accessors where lane numbers route a scatter, kept
    here so ``dma.plan_commit`` stays lane-layout-agnostic. Traces
    inside the Pallas chunk-step body (pure ``jnp.where`` chain, no
    captured device constants)."""
    return jnp.where(
        k == 0, DEVICE,
        jnp.where(k == 1, FRAME,
                  jnp.where(k == 2, EPOCH,
                            jnp.where(k == 3, WEAR, FLAGS))))


def set_flags(table: jax.Array, pages, bits: int) -> jax.Array:
    """OR ``bits`` into the FLAGS lane of ``pages`` (scenario/middleware
    side — the hot path never writes FLAGS)."""
    pages = jnp.asarray(pages, jnp.int32)
    cur = flags_at(table, pages)
    return set_at(table, pages, FLAGS, cur | jnp.int32(bits))


def clear_flags(table: jax.Array, pages, bits: int = KNOWN_FLAGS) -> jax.Array:
    """Clear ``bits`` (default: all known bits) on ``pages``."""
    pages = jnp.asarray(pages, jnp.int32)
    cur = flags_at(table, pages)
    return set_at(table, pages, FLAGS, cur & ~jnp.int32(bits))


def pack_rows(device, frame, hotness=None, wear=None, owner=None,
              epoch=None, flags=None) -> jax.Array:
    """Pack per-lane arrays into a table. Unspecified lanes default to
    zero (the pad lane always does). Inverse of :func:`unpack`."""
    device = jnp.asarray(device, jnp.int32)
    z = jnp.zeros_like(device)
    lanes = [device, jnp.asarray(frame, jnp.int32)]
    for lane in (hotness, wear, owner, epoch, flags):
        lanes.append(z if lane is None else jnp.asarray(lane, jnp.int32))
    lanes.append(z)  # _PAD
    return jnp.stack(lanes, axis=-1)


def unpack(table: jax.Array) -> TableRows:
    """Split a packed table into named lanes (drops the pad lane)."""
    return TableRows(*(table[..., lane] for lane in range(len(LANES))))


def init_table(cfg: EmulatorConfig, n_fast_pages=None,
               pin_fast_fraction=None) -> jax.Array:
    """Initial packed table: the first ``n_fast_pages`` of the flat space
    map to DRAM frames, the rest to NVM frames (the paper's BAR window
    layout maps the two DIMMs contiguously). Fast frame ``f`` starts owned
    by page ``f``; hotness/wear/epoch start at zero.

    ``n_fast_pages`` may be a traced int32 (``RuntimeParams.n_fast_pages``)
    — the total space is static but the tier boundary is a runtime design
    axis. Defaults to ``cfg.n_fast_pages``. ``pin_fast_fraction`` (also
    traceable — ``RuntimeParams.pin_fast_fraction``) pins that share of
    the fast tier with ``PIN_FAST``, modelling §III-G-hinted allocations
    that must stay in DRAM; 0.0 leaves the FLAGS lane all-zero.
    """
    n = cfg.n_pages
    nf = cfg.n_fast_pages if n_fast_pages is None else n_fast_pages
    frac = (cfg.pin_fast_fraction if pin_fast_fraction is None
            else pin_fast_fraction)
    ar = jnp.arange(n)
    dev = jnp.where(ar < nf, FAST, SLOW).astype(jnp.int32)
    frm = jnp.where(ar < nf, ar, ar - nf).astype(jnp.int32)
    n_pin = jnp.floor(jnp.float32(frac) *
                      jnp.asarray(nf, jnp.float32)).astype(jnp.int32)
    flg = jnp.where(ar < n_pin, PIN_FAST, 0).astype(jnp.int32)
    return pack_rows(dev, frm, owner=ar.astype(jnp.int32), flags=flg)


def check_table(cfg: EmulatorConfig, table: np.ndarray,
                n_fast_pages: int | None = None) -> None:
    """Invariants of a packed table:

    * the (device, frame) mapping is a bijection onto device frames —
      every fast and slow frame is owned by exactly one page;
    * the OWNER lane is the exact inverse of the fast-tier mapping;
    * the FLAGS lane carries only known bits, never both pin bits at
      once, and every pin bit agrees with the page's DEVICE lane (a
      PIN_FAST page on the slow tier means a pinned page migrated);
    * RETIRED implies POISONED (a tombstone is always on a dead frame)
      and no page is both PINNED and POISONED (retirement force-clears
      pins, so a pinned page never sits on a poisoned frame);
    * the accumulator lanes saturate: ``0 <= HOTNESS <= HOTNESS_CAP``
      and ``0 <= WEAR <= WEAR_CAP`` — the runtime half of the contract
      the ``ranges`` static pass proves from the same two constants.

    Raises on violation (used by tests and the emulator's debug mode).
    """
    nf = cfg.n_fast_pages if n_fast_pages is None else int(n_fast_pages)
    ns = cfg.n_pages - nf
    table = np.asarray(table)
    dev = table[..., DEVICE]
    frm = table[..., FRAME]
    fast_frames = np.sort(frm[dev == FAST])
    slow_frames = np.sort(frm[dev == SLOW])
    if fast_frames.size != nf or \
            not np.array_equal(fast_frames, np.arange(nf)):
        raise AssertionError("fast-frame mapping is not a bijection")
    if slow_frames.size != ns or \
            not np.array_equal(slow_frames, np.arange(ns)):
        raise AssertionError("slow-frame mapping is not a bijection")
    own = table[..., OWNER]
    for f in range(nf):
        p = own[f]
        if not 0 <= p < cfg.n_pages or dev[p] != FAST or frm[p] != f:
            raise AssertionError(
                f"OWNER lane stale: fast frame {f} claims page {p}")
    flg = table[..., FLAGS]
    bad = np.nonzero(flg & ~KNOWN_FLAGS)[0]
    if bad.size:
        raise AssertionError(
            f"unknown FLAGS bits on page {bad[0]}: {flg[bad[0]]:#x}")
    both = np.nonzero((flg & PINNED) == PINNED)[0]
    if both.size:
        raise AssertionError(
            f"page {both[0]} pinned to both tiers ({flg[both[0]]:#x})")
    stray = np.nonzero(((flg & PIN_FAST) != 0) & (dev != FAST))[0]
    if stray.size:
        raise AssertionError(
            f"PIN_FAST page {stray[0]} migrated to the slow tier")
    stray = np.nonzero(((flg & PIN_SLOW) != 0) & (dev != SLOW))[0]
    if stray.size:
        raise AssertionError(
            f"PIN_SLOW page {stray[0]} migrated to the fast tier")
    orphan = np.nonzero(((flg & RETIRED) != 0) & ((flg & POISONED) == 0))[0]
    if orphan.size:
        raise AssertionError(
            f"RETIRED page {orphan[0]} is not POISONED ({flg[orphan[0]]:#x})")
    hot = np.nonzero(((flg & PINNED) != 0) & ((flg & POISONED) != 0))[0]
    if hot.size:
        raise AssertionError(
            f"page {hot[0]} is pinned on a poisoned frame "
            f"({flg[hot[0]]:#x})")
    for lane, cap, name in ((HOTNESS, HOTNESS_CAP, "HOTNESS"),
                            (WEAR, WEAR_CAP, "WEAR")):
        vals = table[..., lane]
        bad = np.nonzero((vals < 0) | (vals > cap))[0]
        if bad.size:
            raise AssertionError(
                f"{name} lane of row {bad[0]} outside [0, {name}_CAP]: "
                f"{vals[bad[0]]} (wrapped or unsaturated accumulator)")


class HybridAllocator:
    """Host-side allocator over the flat hybrid space with placement hints.

    Mirrors the paper's driver+jemalloc middleware: allocations are ranges
    of flat pages; ``hint`` expresses device preference honoured on a
    best-effort basis (like the extended malloc API of §III-G).
    """

    def __init__(self, cfg: EmulatorConfig):
        self.cfg = cfg
        # Free pools of flat page numbers whose *initial* mapping is on the
        # given device.
        self._free = {
            FAST: list(range(cfg.n_fast_pages - 1, -1, -1)),
            SLOW: list(range(cfg.n_pages - 1, cfg.n_fast_pages - 1, -1)),
        }
        self._owned: dict[int, list[int]] = {}
        self._pinned: dict[int, list[int]] = {}
        self._retired: set[int] = set()
        self._next_handle = 0

    def alloc(self, n_pages: int, hint: int = FAST,
              pin: bool = False) -> tuple[int, np.ndarray]:
        """Allocate ``n_pages`` flat pages, preferring ``hint`` device.
        Returns (handle, page_numbers).

        ``pin=True`` is the strong form of the paper's placement hint:
        each page is nailed to the device it actually landed on (PIN_FAST
        below the tier boundary, PIN_SLOW above — a spilled page pins
        where it spilled). Call :meth:`apply_flags` to stamp the pin bits
        of every live pinned allocation into a packed table's FLAGS lane;
        :meth:`free` releases the pins for subsequent ``apply_flags``
        calls."""
        other = SLOW if hint == FAST else FAST
        take = []
        for pool in (self._free[hint], self._free[other]):
            while pool and len(take) < n_pages:
                take.append(pool.pop())
        if len(take) < n_pages:
            for p in take:  # roll back
                self._free[FAST if p < self.cfg.n_fast_pages else SLOW].append(p)
            raise MemoryError(f"out of hybrid memory ({n_pages} pages)")
        handle = self._next_handle
        self._next_handle += 1
        self._owned[handle] = take
        if pin:
            self._pinned[handle] = take
        return handle, np.asarray(take, np.int32)

    def free(self, handle: int) -> None:
        self._pinned.pop(handle, None)
        for p in self._owned.pop(handle):
            if p in self._retired:
                continue  # dead frames never return to the free pools
            self._free[FAST if p < self.cfg.n_fast_pages else SLOW].append(p)

    def retire(self, pages) -> None:
        """Take ``pages`` permanently out of circulation (their frames
        died — emulation reported them POISONED/RETIRED). Free copies are
        removed from the pools immediately; owned copies are dropped when
        their handle is freed. Capacity degrades gracefully: subsequent
        allocations simply see smaller pools."""
        dead = {int(p) for p in np.atleast_1d(np.asarray(pages, np.int64))}
        self._retired.update(dead)
        for d in (FAST, SLOW):
            self._free[d] = [p for p in self._free[d] if p not in dead]

    @property
    def retired_pages(self) -> set[int]:
        return set(self._retired)

    def apply_flags(self, table: jax.Array) -> jax.Array:
        """Stamp the pin bits of every live pinned allocation into
        ``table``'s FLAGS lane (device chosen per page from its *initial*
        placement, which is where the page still is — pins are applied
        before emulation moves anything). Returns the updated table."""
        nf = self.cfg.n_fast_pages
        fast = [p for ps in self._pinned.values() for p in ps if p < nf]
        slow = [p for ps in self._pinned.values() for p in ps if p >= nf]
        if fast:
            table = set_flags(table, np.asarray(fast, np.int32), PIN_FAST)
        if slow:
            table = set_flags(table, np.asarray(slow, np.int32), PIN_SLOW)
        return table

    @property
    def free_pages(self) -> dict[int, int]:
        return {d: len(v) for d, v in self._free.items()}
