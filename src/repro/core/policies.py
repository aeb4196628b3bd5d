"""Data placement / migration / pattern-recognition policies (paper §III-A).

The paper's platform exists so users can drop *their own* policies into the
HMMU pipeline. A policy here is a pure function examining the chunk's
access stream plus the policy state, and proposing (at most) one page swap
for the single DMA engine — exactly the three policy aspects the paper
names: access-pattern recognition, data placement, data migration.

Policy state is the packed redirection table (``core.table``): hotness
counters ride the HOTNESS lane, the CLOCK inverse map rides the OWNER
lane, placement is the DEVICE lane — policies read named lanes, never raw
columns.

Hardware faithfulness note: policies only use O(chunk) work plus O(1)
row lookups — promotion candidates come from the *current* access stream
(what the RTL pipeline sees), and victims come from a CLOCK-style
round-robin pointer over DRAM frames (the OWNER lane inverse map), not
from a global argmin no RTL could compute in a cycle. A global-scan
variant ("hotness_global") is kept as an idealized reference policy for
design-space studies.

Policy interface::

    propose(cfg, params, table, ptr, pages, is_write, valid)
        -> (want: bool[], slow_page: int32[], fast_victim: int32[], new_ptr)

A policy may additionally declare a keyword parameter named ``min_wear``
(see ``wear_level``): the emulator detects it by signature inspection at
trace time and passes the maintained global min-wear register
(``EmulatorState.min_wear``). Plain seven-argument policies keep working
unchanged.

``cfg`` carries static geometry, ``params`` the traced knobs
(``hot_threshold``, ``n_fast_pages``, ...), ``table`` the metadata store
in either of its forms (the packed ``int32[n_pages, ROW_W]``, or the flat
lane-major form the emulator's scan carries): policies read it only
through ``core.table``'s accessors, which take both. New policies register via
``@register("name")``; the emulator dispatches on the traced
``params.policy_id`` with ``jax.lax.switch`` over the registration order,
which makes the policy itself a batchable design axis (sweeps evaluate
several policies in one compiled computation).

``new_ptr`` is the CLOCK pointer the policy wants, under a two-case
commit contract enforced by the emulator:

* ``want`` proposals only commit ``new_ptr`` when the swap actually
  *starts* (the emulator re-masks ``want`` — validity, device sanity,
  pin bits — and the single DMA engine may be busy): a rejected or
  dropped proposal leaves the pointer where it was, so no usable victim
  frame is silently consumed;
* with ``want`` False, ``new_ptr`` commits unconditionally — that is the
  channel for skipping a *pinned* CLOCK frame (a pinned frame is not in
  the victim rotation at all, so stepping past it consumes nothing; a
  policy that never skips just returns ``ptr``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from . import table as table_lib
from .config import FAST, SLOW

POLICIES: dict[str, Callable] = {}


def register(name: str):
    def deco(fn):
        POLICIES[name] = fn
        return fn
    return deco


def get(name: str) -> Callable:
    if name not in POLICIES:
        raise KeyError(f"unknown policy {name!r}; have {sorted(POLICIES)}")
    return POLICIES[name]


def policy_id(name: str) -> int:
    """Index of ``name`` in registration order — the ``lax.switch`` branch
    index carried by ``RuntimeParams.policy_id``."""
    get(name)
    return list(POLICIES).index(name)


@dataclasses.dataclass(frozen=True)
class PolicyRegistry:
    """An immutable ``name -> policy fn`` snapshot — the unit the compiled
    pipeline dispatches over.

    The mutable module dict above stays the *registration* surface
    (``@register`` keeps working), but nothing compiled ever reads it:
    ``repro.Engine`` and the legacy wrappers take a snapshot at
    construction/call time, and the ``lax.switch`` branches are built from
    the snapshot's own function tuple. A late ``@register`` (or a
    re-registration of an existing name) therefore changes *future*
    snapshots only — it can neither invalidate nor silently leak into an
    existing session's compiled executables, which is exactly the
    import-order hazard the old global-dict lookups had.

    Frozen + tuple-valued, so a registry is hashable and usable as a jit
    static argument; two snapshots of an unchanged global dict compare
    equal and share compilations.
    """

    names: tuple[str, ...]
    fns: tuple[Callable, ...]

    def __post_init__(self):
        if len(self.names) != len(self.fns):
            raise ValueError("names and fns length mismatch")
        if len(set(self.names)) != len(self.names):
            raise ValueError(f"duplicate policy names: {self.names}")

    @classmethod
    def snapshot(cls, names=None) -> "PolicyRegistry":
        """Snapshot the global registration dict (all registered policies,
        in registration order, when ``names`` is None; else the named
        subset in the given order)."""
        if names is None:
            names = tuple(POLICIES)
        return cls(tuple(names), tuple(get(n) for n in names))

    def index(self, name: str) -> int:
        """Branch index of ``name`` — what ``RuntimeParams.policy_id``
        must carry for this registry."""
        if name not in self.names:
            raise KeyError(
                f"policy {name!r} is not in this registry; have {self.names}")
        return self.names.index(name)

    def subset(self, names) -> "PolicyRegistry":
        """A restricted registry carrying the same snapshotted functions
        (sweeps compile the switch only over policies actually present)."""
        return PolicyRegistry(tuple(names),
                              tuple(self.fns[self.index(n)] for n in names))

    def __contains__(self, name) -> bool:
        return name in self.names

    def __len__(self) -> int:
        return len(self.names)

    def __iter__(self):
        return iter(self.names)



def update_hotness(p, table: jax.Array, pages: jax.Array,
                   is_write: jax.Array, valid: jax.Array,
                   do_decay: jax.Array,
                   write_weight: jax.Array | int | None = None) -> jax.Array:
    """Scatter-add chunk accesses into the HOTNESS lane, then
    decay-by-shift on ``do_decay`` boundaries (hardware aging counters).
    ``p`` is an ``EmulatorConfig`` or traced ``RuntimeParams`` (shared
    field names).

    ``write_weight`` overrides ``p.write_weight`` — the emulator passes
    the *policy-scoped* effective weight (``p.write_weight`` only when the
    active policy is ``write_bias``, else 1), so the weighting is part of
    the write_bias policy rather than a global knob that silently changes
    every other policy's hotness accounting."""
    ww = p.write_weight if write_weight is None else write_weight
    w = 1 + (ww - 1) * is_write.astype(jnp.int32)
    w = jnp.where(valid, w, 0)
    table = table_lib.add_hotness(table, pages, w)
    return jax.lax.cond(
        do_decay,
        lambda t: table_lib.decay_hotness(t, p.hotness_decay_shift),
        lambda t: t, table)


def _chunk_candidate(table, pages, valid, extra_mask=None):
    """Hottest slow-resident page among this chunk's accesses. Pinned
    pages (PIN_SLOW — nailed to NVM) and retirement tombstones (parked on
    dead frames) are never candidates; the emulator would veto them
    anyway, and a vetoed hottest page would livelock the proposal stream.
    ``extra_mask`` further restricts eligibility (wear_level's
    destination freshness)."""
    rows = table_lib.rows(table, pages)
    ok = valid & (table_lib.device(rows) == SLOW) & \
        ~table_lib.is_pinned(rows) & ~table_lib.is_retired(rows)
    if extra_mask is not None:
        ok = ok & extra_mask
    heat = jnp.where(ok, table_lib.hotness(rows), -1)
    j = jnp.argmax(heat)
    return pages[j], heat[j]


# CLOCK pin-skip lookahead: how many frames from the pointer a policy
# examines per chunk to find an unpinned victim (an 8-wide pin-bit
# priority encoder in RTL terms). Pinned frames are not in the victim
# rotation; without lookahead a long pinned run (pin_fast_fraction pins
# a contiguous prefix) would stall migration one chunk per frame.
_CLOCK_WINDOW = 8


def _clock_victim(table, ptr, nf):
    """First eligible CLOCK victim within ``_CLOCK_WINDOW`` frames of the
    pointer — pinned owners and retirement tombstones (a dead fast frame
    is permanently out of the victim rotation) are stepped over alike.
    Returns ``(victim_page, found, skip)`` where ``skip`` is the
    number of skipped frames stepped over to reach it (== the window width
    when every probed frame is ineligible and ``found`` is False).

    Policies fold it into the pointer-commit contract as
    ``new_ptr = (ptr + skip + want) % nf``: the pinned run is consumed
    unconditionally (``want=False`` commits unconditionally, and a
    started swap consumes it along with the victim), while the victim
    itself is only consumed by a started swap. With no pins ``skip`` is 0
    and the arithmetic reduces exactly to the classic ``ptr + want``."""
    offs = jnp.arange(_CLOCK_WINDOW, dtype=jnp.int32)
    frames = (ptr + offs) % nf
    owners = table_lib.lane_at(table, frames, table_lib.OWNER)
    rows = table_lib.rows(table, owners)
    pinned = table_lib.is_pinned(rows) | table_lib.is_retired(rows)
    first = jnp.argmin(pinned).astype(jnp.int32)   # first False, else 0
    found = ~pinned[first]
    victim = owners[first]
    skip = jnp.where(found, first, _CLOCK_WINDOW)
    return victim, found, skip


@register("static")
def static_policy(cfg, params, table, ptr, pages, is_write, valid):
    """Placement fixed at initialization; never migrate (the baseline the
    paper's users compare their designs against)."""
    z = jnp.zeros((), jnp.int32)
    return jnp.zeros((), bool), z, z, ptr


@register("hotness")
def hotness_policy(cfg, params, table, ptr, pages, is_write, valid):
    """Promote the hottest slow page seen in this chunk once it crosses
    ``hot_threshold``; victim = CLOCK pointer over DRAM frames, skipped if
    the victim is hotter than the candidate. Pinned frames at the pointer
    are stepped over without a proposal (they are not victims)."""
    cand, heat = _chunk_candidate(table, pages, valid)
    victim, vfound, skip = _clock_victim(table, ptr, params.n_fast_pages)
    want = vfound & (heat >= params.hot_threshold) & \
        (heat > table_lib.hotness_at(table, victim))
    new_ptr = (ptr + skip + want.astype(jnp.int32)) % params.n_fast_pages
    return want, cand, victim, new_ptr


@register("write_bias")
def write_bias_policy(cfg, params, table, ptr, pages, is_write, valid):
    """Same promotion rule as ``hotness``, but hotness accumulation
    weights writes by ``params.write_weight`` (configure > 1) — and ONLY
    this policy applies the weight (the emulator scopes it by the traced
    ``policy_id``, so a policy-axis sweep of hotness vs write_bias at
    equal ``write_weight`` actually diverges). NVM writes are the
    expensive, endurance-limited operation (paper Table I), so
    write-heavy pages should live in DRAM."""
    return hotness_policy(cfg, params, table, ptr, pages, is_write, valid)


@register("stream")
def stream_policy(cfg, params, table, ptr, pages, is_write, valid):
    """Access-pattern recognition: detect a dominant small stride in the
    chunk's page stream and *pre-promote* the stream's next page before
    demand accesses pay NVM latency (prefetch-style migration). Falls back
    to the hotness rule when no stream is detected."""
    deltas = jnp.where(valid[1:] & valid[:-1], pages[1:] - pages[:-1], 0)
    span = 4  # recognise strides in [-span, span] \ {0}
    in_range = (jnp.abs(deltas) <= span) & (deltas != 0)
    hist = jnp.zeros(2 * span + 1, jnp.int32).at[
        jnp.clip(deltas + span, 0, 2 * span)].add(
        in_range.astype(jnp.int32), mode="drop")
    stride = jnp.argmax(hist).astype(jnp.int32) - span
    strength = jnp.max(hist)
    streaming = strength > (pages.shape[0] // 4)

    last = pages[jnp.argmax(jnp.where(valid, jnp.arange(pages.shape[0]), -1))]
    target = jnp.clip(last + stride, 0, table_lib.n_pages(table) - 1)
    target_row = table_lib.rows(table, target)
    target_is_slow = (table_lib.device(target_row) == SLOW) & \
        ~table_lib.is_pinned(target_row) & ~table_lib.is_retired(target_row)

    hw, hc, _, _ = hotness_policy(cfg, params, table, ptr, pages, is_write,
                                  valid)
    victim, vfound, skip = _clock_victim(table, ptr, params.n_fast_pages)
    want_stream = streaming & target_is_slow & vfound
    want = want_stream | hw
    cand = jnp.where(want_stream, target, hc)
    new_ptr = (ptr + skip + want.astype(jnp.int32)) % params.n_fast_pages
    return want, cand, victim, new_ptr


@register("hotness_global")
def hotness_global_policy(cfg, params, table, ptr, pages, is_write, valid):
    """Idealized reference: global hottest-slow / coldest-fast scan each
    chunk. No RTL implements this in a cycle — kept for design-space
    comparison against the realizable policies above."""
    dev = table_lib.column(table, table_lib.DEVICE)
    hot = table_lib.column(table, table_lib.HOTNESS)
    flg = table_lib.column(table, table_lib.FLAGS)
    pinned = (flg & (table_lib.PINNED | table_lib.RETIRED)) != 0
    heat_all = jnp.where((dev == SLOW) & ~pinned, hot, -1)
    cand = jnp.argmax(heat_all).astype(jnp.int32)
    heat = heat_all[cand]
    cold = jnp.where((dev == FAST) & ~pinned, hot, 2 ** 30)
    victim = jnp.argmin(cold).astype(jnp.int32)
    want = (heat >= params.hot_threshold) & (heat > hot[victim])
    return want, cand, victim, ptr


@register("wear_level")
def wear_level_policy(cfg, params, table, ptr, pages, is_write, valid,
                      min_wear=None):
    """Endurance-aware promotion (paper Table I's write-endurance
    asymmetry as a first-class policy axis): same hottest-page promotion
    rule as ``hotness``, but the demotion *destination* is chosen
    wear-aware. A swap demotes the CLOCK victim into the candidate's slow
    frame, and that frame absorbs the full-page migration write plus the
    victim's future demand writes — so candidates whose frame has already
    absorbed more than ``params.wear_slack`` writes beyond the global
    minimum are skipped, steering migration traffic toward fresh frames
    and flattening the WEAR histogram (max-lifetime leveling) at
    near-equal hit rate.

    ``min_wear`` is the emulator-maintained global min-wear register
    (``EmulatorState.min_wear``): the true minimum over every slow
    frame's WEAR, refreshed at decay boundaries (a hardware-style
    periodic scrub riding the aging tick — between refreshes the
    register is stale but monotone, since wear only grows, so the
    ``wear_slack`` band is conservative by at most one decay period's
    writes). ``wear_slack`` is therefore measured against the *whole
    histogram's* floor; policies invoked outside the emulator (tests,
    notebooks) may pass ``min_wear=None`` to fall back to the historical
    chunk-local floor over this chunk's slow frames."""
    rows = table_lib.rows(table, pages)
    slow = valid & (table_lib.device(rows) == SLOW)
    frm = table_lib.frame(rows)
    # WEAR is keyed by slow frame: one O(chunk) gather of the candidates'
    # frame rows (the page rows above are the stage-2-style gather every
    # chunk-local policy already pays).
    frame_wear = table_lib.wear_at(table, jnp.where(slow, frm, 0))
    if min_wear is None:
        wmin = jnp.min(jnp.where(slow, frame_wear, 2 ** 30))
    else:
        wmin = min_wear
    fresh = frame_wear <= wmin + params.wear_slack
    cand, cheat = _chunk_candidate(table, pages, valid, extra_mask=fresh)
    victim, vfound, skip = _clock_victim(table, ptr, params.n_fast_pages)
    want = vfound & (cheat >= params.hot_threshold) & \
        (cheat > table_lib.hotness_at(table, victim))
    new_ptr = (ptr + skip + want.astype(jnp.int32)) % params.n_fast_pages
    return want, cand, victim, new_ptr
