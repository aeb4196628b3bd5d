"""One-kernel chunk step: the whole HMMU pipeline as a single Pallas call.

The paper's HMMU resolves a request per cycle because lookup, bank
arbitration, and migration control live in ONE pipeline next to BRAM.
This module is that pipeline's software twin, written once and executed
two ways:

* :func:`step_ref` — the composable jnp "scan path": closed-form max-plus
  scans (``core.latency``), the batched lookup kernel for stage 2, one
  combined boundary scatter for every table write. This is what
  ``core.emulator`` runs by default on CPU.
* the Pallas path — ``pl.pallas_call`` with the packed redirection table
  staged through VMEM, the sequential max-plus recurrences expressed as
  in-kernel ``fori_loop``s (the RTL formulation, not the closed form),
  the scalar state and ``RuntimeParams`` riding a scalar-prefetch int
  vector (``policy_id`` dispatch included), and a leading batch axis +
  ``custom_vmap`` rule so a vmapped design-space sweep launches ONE
  kernel per chunk for all points. Interpret mode off-TPU.

Both paths are bitwise identical on every knob combination (property
tests in tests/test_chunk_step_kernel.py): all pipeline arithmetic is
exact int32, and the sequential recurrences are provably equal to the
associative closed forms.

The one true chunk schedule (the ordering contract the kernel implements
and ``core.emulator`` documents):

1. **Reads** — every table read of the chunk happens against the
   *pre-chunk* table: the stage-2 row gather (chunk pages + DMA swap
   pair), the swap pair's DEVICE/FRAME/EPOCH pre-values consumed by
   ``dma.plan_commit``, and the OWNER pre-value of the promoted frame.
2. **Boundary commit** — every table write lands in ONE 2-D
   (row, lane) scatter-add over exact int32 deltas (hotness
   accumulation, demand-write WEAR, the swap commit's lane exchanges,
   the OWNER inverse-map update routed through a ``mode="drop"``
   sentinel row), followed by the decay shift. One in-place update
   instead of ~a dozen copying scatters — the restructure that makes the
   scan path fast and the kernel possible. The table is never reshaped
   to a flat view: a TPU pads each 8-lane row to 128 lanes, so a flat
   view relays out the whole padded table on the way in and out.
3. **Retire** — the retirement subsystem (:func:`retire_phase`) reads
   the committed table and stamps at most one dying frame's resident
   page POISONED: a second, sentinel-guarded single-row FLAGS scatter —
   the one documented extension to the "one scatter" rule, a dropped
   no-op whenever retirement is idle.
4. **Policy** — the proposal phase reads the *committed* table (policies
   see this chunk's accesses and completed migration, exactly as
   before), then ``dma.maybe_start`` and the CLOCK pointer commit. A
   pending rescue preempts the policy's proposal on the single DMA
   channel.

Nothing mid-pipeline reads a mid-chunk write; FLAGS is only written at
boundaries (the swap commit's poison travel and the retirement stamp),
never on the hot path.

TPU note: the TPU compiler refuses the kernel ("Can only load scalars
from SMEM": the body reads a vector from the scalar-prefetch operand).
Behind that, the whole table is declared as both an input and an output
VMEM block (2 x 9.4 MB at paper geometry, with no
``input_output_aliases`` and no ``vmem_limit_bytes``), and the body
gathers, scatters and sorts by value index. So "auto" never selects it
on a TPU (``kernels.ops.REFUSED_ON_TPU``); it runs in interpret mode
off-TPU, where the bit-identity suite exercises it.
"""
from __future__ import annotations

import functools
import inspect
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import consistency, dma as dma_lib, latency
from repro.core import faults as faults_lib
from repro.core import policies as policies_lib
from repro.core import table as table_lib
from repro.core.config import FAST, SLOW, EmulatorConfig, RuntimeParams
from repro.core.policies import PolicyRegistry
from . import ops as kernel_ops

# Python literals, NOT eager jnp arrays: everything below also traces
# inside the Pallas body, which rejects captured device constants.
_MIN = -(2 ** 31)
_NEG = -(2 ** 30)  # == int(_NEG), the invalid-slot arrival time

# VMEM the resident table may claim before "auto" falls back to the scan
# path (a TPU core has ~16 MB; leave room for the chunk vectors + double
# buffering of the blocked operands).
VMEM_TABLE_BUDGET = 12 * 2 ** 20


class StepScalars(NamedTuple):
    """The scalar slice of ``EmulatorState`` a chunk step carries (the
    packed table and ``bank_free`` travel separately; counters stay in
    the emulator — float accumulation never enters the kernel).

    The trailing three registers are the retirement subsystem's state
    (rescue register, global min-wear register, FaultPlan death cursor);
    they default so pre-retirement callers constructing scalars by
    keyword keep working."""
    clock: jax.Array
    clock_ptr: jax.Array
    chunk_idx: jax.Array
    dma: dma_lib.DMAState
    link_free_rx: jax.Array
    link_free_tx: jax.Array
    last_return: jax.Array
    rescue_page: jax.Array = -1   # page awaiting rescue off a dead frame
    min_wear: jax.Array = 0       # global min slow-frame WEAR (scrubbed)
    fault_cursor: jax.Array = 0   # next unconsumed FaultPlan death row


class PipelineOut(NamedTuple):
    """Everything the pipeline phase hands the boundary phases."""
    dev: jax.Array        # int32[chunk] — device actually accessed
    frm: jax.Array        # int32[chunk] — frame actually accessed
    row_a: jax.Array      # int32[W] — pre-chunk row of DMA swap member a
    row_b: jax.Array      # int32[W] — pre-chunk row of DMA swap member b
    returns: jax.Array    # int32[chunk] — TX return time (unmasked)
    lat: jax.Array        # int32[chunk] — request latency (masked)
    held: jax.Array       # int32 — responses delayed by tag matching
    poisoned: jax.Array   # bool[chunk] — touched a POISONED page
    bank_free: jax.Array  # int32[2*n_banks] — post-chunk bank busy times
    rx_last: jax.Array    # int32 — RX link busy-until after the chunk
    tx_last: jax.Array    # int32 — TX link busy-until after the chunk
    hot_pre: jax.Array    # int32[chunk] — pre-chunk HOTNESS of the pages
    #   (commit_phase saturates the hotness scatter against it)


# --------------------------------------------------------------------------- #
# sequential (in-kernel) formulations of the ordering-sensitive stages
# --------------------------------------------------------------------------- #
# Each is the direct RTL recurrence; ``core.latency`` proves the closed
# forms equal, so these are bitwise-identical on int32 (no float anywhere).

def _seq_maxplus(arrival: jax.Array, service: jax.Array) -> jax.Array:
    """``done_i = max(arrival_i, done_{i-1}) + service_i`` as a loop."""
    n = arrival.shape[0]

    def body(i, carry):
        prev, done = carry
        d = jnp.maximum(arrival[i], prev) + service[i]
        return d, done.at[i].set(d)

    init = (jnp.full((), _MIN, jnp.int32), jnp.zeros(n, jnp.int32))
    return jax.lax.fori_loop(0, n, body, init)[1]


def _seq_bank_resolve(arrival, service, bank, bank_free):
    """One pass over the chunk with a live ``bank_free`` register file —
    what the FPGA's per-bank queue head pointers do. Equal to the dense
    one-hot resolver: folding ``bank_free`` into only the first request
    of each bank suffices because done times never drop below the seed
    (service >= 0)."""
    n = arrival.shape[0]
    arr = jnp.maximum(arrival, _NEG)

    def body(i, carry):
        free, done = carry
        d = jnp.maximum(arr[i], free[bank[i]]) + service[i]
        return free.at[bank[i]].set(d), done.at[i].set(d)

    free, done = jax.lax.fori_loop(
        0, n, body, (bank_free, jnp.zeros(n, jnp.int32)))
    return done, free


def _seq_inorder(complete: jax.Array, last_return: jax.Array) -> jax.Array:
    """Running max over ``max(complete_i, last_return)`` — the HDR-FIFO
    tag match as a loop."""
    n = complete.shape[0]

    def body(i, carry):
        run, out = carry
        r = jnp.maximum(jnp.maximum(complete[i], last_return), run)
        return r, out.at[i].set(r)

    init = (jnp.full((), _MIN, jnp.int32), jnp.zeros(n, jnp.int32))
    return jax.lax.fori_loop(0, n, body, init)[1]


# --------------------------------------------------------------------------- #
# phase 1: the request pipeline (pure reads)
# --------------------------------------------------------------------------- #

def pipeline_phase(cfg: EmulatorConfig, params: RuntimeParams,
                   table: jax.Array, sc: StepScalars, bank_free: jax.Array,
                   page, offset, is_write, size, valid, *,
                   seq: bool = False) -> PipelineOut:
    """Stages 1-5 of the paper's Fig 2 workflow: RX link, table lookup +
    DMA-conflict redirect, bank queues + media access, tag-match in-order
    return, TX link. Touches the table READ-ONLY (schedule contract §1).
    Each stage runs under its own named scope (``rx``, ``lookup``,
    ``banks``, ``return``, ``tx``).

    ``seq=True`` selects the in-kernel sequential recurrences (the Pallas
    body); default is the closed-form scan path.
    """
    n = page.shape[0]
    mp = _seq_maxplus if seq else latency.maxplus_scan

    # --- stage 1: RX link (host -> HMMU). Writes carry payload, reads a
    # header.
    with jax.named_scope("rx"):
        size = jnp.where(valid, size, 0)
        issue = sc.clock + params.issue_gap * (
            1 + jnp.arange(n, dtype=jnp.int32))
        issue = jnp.where(valid, issue, _NEG)
        rx_bytes = jnp.where(is_write, size, 16)
        rx_srv = jnp.where(valid,
                           latency.link_service_cycles(params, rx_bytes), 0)
        rx_done = mp(
            jnp.maximum(issue, jnp.where(valid, sc.link_free_rx, _NEG)),
            rx_srv)
        arrive = rx_done + jnp.where(valid, params.link_lat // 2, 0)

    # --- stage 2: redirection-table lookup (+ DMA swap-progress redirect).
    # One packed-row fetch — the BRAM read per cycle of the paper's
    # pipeline. The scan path goes through the batched lookup engine
    # (Pallas gather on TPU, jnp elsewhere; the fused flavour appends the
    # DMA swap pair, chunk + 2 rows in one launch). Inside the one-kernel
    # body the table is already VMEM-resident, so the gather is a direct
    # row index. All paths clamp indices identically.
    with jax.named_scope("lookup"):
        a = jnp.maximum(sc.dma.page_a, 0)
        b = jnp.maximum(sc.dma.page_b, 0)
        if seq:
            pg = jnp.clip(page, 0, table.shape[0] - 1)
            rows = table[pg]
            row_a, row_b = table[a], table[b]
        elif cfg.fuse_swap_gather:
            rows, swap_rows = kernel_ops.hmmu_lookup_fused(
                table, page, jnp.stack([a, b]))
            row_a, row_b = swap_rows[..., 0, :], swap_rows[..., 1, :]
        else:
            rows = kernel_ops.hmmu_lookup(table, page)
            row_a, row_b = table[a], table[b]
        dev = table_lib.device(rows)
        frm = table_lib.frame(rows)
        hot_pre = table_lib.hotness(rows)
        dev, frm = dma_lib.redirect(
            cfg, sc.dma, page, offset, arrive, dev, frm, row_a, row_b, params)
        poisoned = valid & table_lib.is_poisoned(rows)

    # --- stage 3: per-device bank queues + media access.
    with jax.named_scope("banks"):
        bank = dev * cfg.n_banks + frm % cfg.n_banks
        med_srv = jnp.where(
            valid, latency.device_service_cycles(params, dev, is_write, size),
            0)
        if seq:
            med_done, bank_free2 = _seq_bank_resolve(arrive, med_srv, bank,
                                                     bank_free)
        else:
            resolve = (latency.resolve_bank_queues_segmented
                       if latency.pick_bank_resolver(cfg) == "segmented"
                       else latency.resolve_bank_queues)
            med_done, bank_free2 = resolve(
                arrive, med_srv, bank, 2 * cfg.n_banks, bank_free)

    # --- stage 4: tag-match in-order return (paper §III-C) ...
    with jax.named_scope("return"):
        inorder = _seq_inorder if seq else consistency.in_order_returns
        ordered = inorder(jnp.where(valid, med_done, _NEG),
                          sc.last_return)
        held = jnp.sum((ordered > med_done) & valid).astype(jnp.int32)

    # --- stage 5: ... then TX link serialization (responses leave in
    # order).
    with jax.named_scope("tx"):
        tx_bytes = jnp.where(is_write, 16, size)
        tx_srv = jnp.where(valid,
                           latency.link_service_cycles(params, tx_bytes), 0)
        returns = mp(
            jnp.maximum(ordered, jnp.where(valid, sc.link_free_tx, _NEG)),
            tx_srv) + jnp.where(valid, params.link_lat // 2, 0)
        lat = jnp.where(valid, returns - issue, 0)
    return PipelineOut(dev, frm, row_a, row_b, returns, lat, held, poisoned,
                       bank_free2, rx_done[-1], returns[-1], hot_pre)


# --------------------------------------------------------------------------- #
# phase 2: the boundary commit (pure writes — ONE combined scatter)
# --------------------------------------------------------------------------- #

def eff_write_weight(params: RuntimeParams, registry: PolicyRegistry):
    """Policy-scoped hotness write weighting: only the ``write_bias``
    policy biases hotness by ``write_weight``; every other policy counts
    reads and writes equally, so the policy axis is a real comparison."""
    if "write_bias" in registry.names:
        return jnp.where(params.policy_id == registry.index("write_bias"),
                         params.write_weight, 1)
    return 1


def commit_phase(cfg: EmulatorConfig, params: RuntimeParams,
                 table: jax.Array, sc: StepScalars, pipe: PipelineOut,
                 page, is_write, valid, eff_weight):
    """Commit the chunk to the table: hotness accumulation, demand-write
    WEAR, the DMA swap commit, and the OWNER inverse-map update — all as
    exact int32 deltas in ONE 2-D (row, lane) scatter-add on the table
    (then the decay shift); no flat view, which a TPU would reach by
    relaying out the table padded to 128 lanes per row. Every delta is
    computed against pre-chunk reads (schedule contract §2), and
    distinct updates target distinct (row, lane) slots except WEAR,
    where duplicate targets sum exactly as the historical sequential
    adds did.

    Retirement extensions (both exactly zero-effect when the subsystem is
    idle): the swap commit's FLAGS triples carry poison travel for the
    page in the rescue register (``dma.plan_commit``), and the global
    min-wear register is rescrubbed on decay boundaries — a periodic
    whole-histogram min over the slow frames' WEAR lane riding the aging
    tick, so ``wear_level``'s slack band is measured against the true
    floor at decay granularity.

    Returns ``(table, dma, done, now, last_ret, min_wear, tombstone)``;
    ``tombstone`` is the page this commit parked on a dead frame (-1 if
    none — when set, the pending rescue completed and the register
    clears).
    """
    n = page.shape[0]
    n_pages = table.shape[0]
    with jax.named_scope("deltas"):
        any_valid = jnp.any(valid)
        last_ret = jnp.where(
            any_valid, jnp.max(jnp.where(valid, pipe.returns, sc.last_return)),
            sc.last_return)
        now = jnp.maximum(sc.clock + params.issue_gap * n, last_ret)

        # Hotness accumulation (decayed below, after the combined scatter —
        # nothing else in the scatter touches the HOTNESS lane). Weights are
        # clipped against the pre-chunk lane value so the counter saturates
        # at HOTNESS_CAP instead of wrapping — exact under duplicate pages,
        # identity below the cap.
        hot_w = 1 + (jnp.asarray(eff_weight, jnp.int32) - 1) * \
            is_write.astype(jnp.int32)
        hot_w = jnp.where(valid, hot_w, 0)
        hot_w = table_lib.saturating_weights(page, hot_w, pipe.hot_pre,
                                             table_lib.HOTNESS_CAP)
        # NVM endurance: demand writes per slow frame (the DMA migration's
        # full-page write is charged by the swap commit's WEAR deltas).
        slow_wr = is_write & valid & (pipe.dev == SLOW)

        # DMA swap commit, planned from the stage-2 prefetched rows.
        swap_a = jnp.maximum(sc.dma.page_a, 0)  # pre-completion swap pair
        plan = dma_lib.plan_commit(cfg, sc.dma, now, pipe.row_a, pipe.row_b,
                                   params, sc.rescue_page)
        # OWNER inverse map (fast frame -> owning page, the CLOCK victim
        # rotation): the promoted page (swap_a, now FAST) owns its new frame.
        # No swap completed => route the write through the out-of-range
        # sentinel row n_pages, dropped by the scatter, so row 0's OWNER
        # lane can never be clobbered by the idle guard index.
        db = table_lib.device(pipe.row_b)
        fb = table_lib.frame(pipe.row_b)
        promoted = plan.done & (db == FAST)
        own_pre = table[fb, table_lib.OWNER]
        own_row = jnp.where(promoted, fb, n_pages)
        own_delta = jnp.where(promoted, swap_a - own_pre, 0)

        # WEAR saturation: demand charges and the swap commit's migration
        # charges can land on the SAME slow frame in one boundary, so both
        # sources join ONE fill-until-full pass against the pre-chunk WEAR
        # (one extra pre-commit single-lane gather — a read, schedule §1).
        # The plan keeps its non-WEAR deltas; its WEAR entries move into the
        # joint fill (scatter-add totals are order-independent, so below the
        # cap this is bitwise the historical commit).
        wear_mask = plan.lanes == table_lib.WEAR
        wear_rows = jnp.concatenate([
            jnp.where(slow_wr, pipe.frm, 0),
            jnp.where(wear_mask, plan.rows, 0)])
        wear_w = jnp.concatenate([
            slow_wr.astype(jnp.int32),
            jnp.where(wear_mask, plan.delta, 0)])
        wear_pre = table[wear_rows, table_lib.WEAR]
        wear_w = table_lib.saturating_weights(wear_rows, wear_w, wear_pre,
                                              table_lib.WEAR_CAP)
        plan_delta = jnp.where(wear_mask, 0, plan.delta)

    with jax.named_scope("scatter"):
        def lane(k, size):
            return jax.lax.full((size,), k, jnp.int32)

        rows = jnp.concatenate([page, wear_rows, plan.rows, own_row[None]])
        lanes = jnp.concatenate([
            lane(table_lib.HOTNESS, n),
            lane(table_lib.WEAR, wear_rows.shape[0]),
            plan.lanes,
            lane(table_lib.OWNER, 1)])
        upd = jnp.concatenate([
            hot_w, wear_w, plan_delta, own_delta[None],
        ])
        table = table.at[rows, lanes].add(upd, mode="drop")

    with jax.named_scope("decay"):
        do_decay = ((sc.chunk_idx % params.decay_every)
                    == (params.decay_every - 1))
        table = jax.lax.cond(
            do_decay,
            lambda t: t.at[:, table_lib.HOTNESS].set(
                t[:, table_lib.HOTNESS] >> params.hotness_decay_shift),
            lambda t: t, table)

    # Min-wear scrub: slow frames are rows [0, n_slow) of the WEAR lane.
    with jax.named_scope("scrub"):
        n_slow = n_pages - params.n_fast_pages
        wmin_global = jnp.min(jnp.where(
            jnp.arange(n_pages, dtype=jnp.int32) < n_slow,
            table[:, table_lib.WEAR], 2 ** 30))
        min_wear = jnp.where(do_decay, wmin_global, sc.min_wear)
    return table, plan.dma, plan.done, now, last_ret, min_wear, \
        plan.tombstone


# --------------------------------------------------------------------------- #
# phase 2.5: endurance-driven frame retirement (reads the committed table)
# --------------------------------------------------------------------------- #

def retire_phase(cfg: EmulatorConfig, params: RuntimeParams,
                 table: jax.Array, sc: StepScalars, rescue_page,
                 fault_cursor, faults: faults_lib.FaultPlan, page, valid):
    """Detect at most ONE frame death per boundary and mark its resident
    page POISONED (pins force-cleared — a dying frame exits every pin
    contract; the serving layer renegotiates). Two detectors, gated on a
    free rescue register (one rescue in flight at a time — the single DMA
    engine):

    * **FaultPlan deaths** (priority): the next death row fires once its
      chunk stamp is due. A due row whose page is already POISONED or a
      RETIRED tombstone is consumed without effect (the frame is already
      dead).
    * **Endurance crossings**: with ``endurance_budget > 0``, any page
      *observed this boundary* (the chunk's accesses plus the in-flight
      swap members — the only rows whose WEAR can have just moved) that
      is slow-resident on a frame whose WEAR exceeds the budget.

    The stamp is one sentinel-guarded single-row FLAGS scatter — the
    documented second boundary write after the combined commit scatter,
    and a dropped no-op whenever nothing fires (``endurance_budget <= 0``
    and an empty plan leave the table bitwise-untouched).

    Returns ``(table, rescue_page, fault_cursor, retired_page)`` with
    ``retired_page`` = the page marked this boundary, else -1.
    """
    n_pages = table.shape[0]
    free = rescue_page < 0

    # FaultPlan death detector (serialized through the cursor).
    deaths = faults.deaths
    nd = deaths.shape[0]
    cur = jnp.minimum(fault_cursor, nd - 1)
    due = (fault_cursor < nd) & (deaths[cur, 0] <= sc.chunk_idx)
    consume = due & free
    ev_p = jnp.clip(deaths[cur, 1], 0, n_pages - 1)
    ev_flags = table[ev_p, table_lib.FLAGS]
    death_fire = consume & \
        ((ev_flags & (table_lib.POISONED | table_lib.RETIRED)) == 0)
    fault_cursor = fault_cursor + consume.astype(jnp.int32)

    # Endurance detector over the boundary's observed pages.
    a, b = sc.dma.page_a, sc.dma.page_b
    cand = jnp.concatenate([
        page, jnp.stack([jnp.maximum(a, 0), jnp.maximum(b, 0)])])
    cand_ok = jnp.concatenate([valid, jnp.stack([a >= 0, b >= 0])])
    cand = jnp.clip(cand, 0, n_pages - 1)
    rows = table[cand]
    wear = table[jnp.where(table_lib.device(rows) == SLOW,
                           table_lib.frame(rows), 0), table_lib.WEAR]
    over = cand_ok & (params.endurance_budget > 0) & \
        (table_lib.device(rows) == SLOW) & \
        (wear > params.endurance_budget) & \
        ((table_lib.flags(rows) &
          (table_lib.POISONED | table_lib.RETIRED)) == 0)
    j = jnp.argmax(over)
    wear_fire = free & ~death_fire & over[j]

    fire = death_fire | wear_fire
    p_ret = jnp.where(death_fire, ev_p, cand[j])
    new_fl = (table[p_ret, table_lib.FLAGS] | table_lib.POISONED) & \
        ~table_lib.PINNED
    table = table.at[jnp.where(fire, p_ret, n_pages),
                     table_lib.FLAGS].set(new_fl, mode="drop")
    rescue_page = jnp.where(fire, p_ret, rescue_page)
    return table, rescue_page, fault_cursor, jnp.where(fire, p_ret, -1)


# --------------------------------------------------------------------------- #
# phase 3: the policy proposal (reads the committed table)
# --------------------------------------------------------------------------- #

def policy_phase(cfg: EmulatorConfig, params: RuntimeParams,
                 registry: PolicyRegistry, table: jax.Array, sc: StepScalars,
                 dma: dma_lib.DMAState, now, page, is_write, valid,
                 rescue_page, min_wear):
    """Policy dispatch on the *traced* policy id: ``lax.switch`` over the
    (static, frozen) registry snapshot makes the policy itself a
    batchable design axis — inside the Pallas body the id arrives via the
    scalar-prefetch vector. A single-policy registry skips the switch.
    Branches come from the snapshot's own function tuple, so
    re-registering a policy name after the snapshot cannot leak into this
    compilation. A branch declaring a ``min_wear`` keyword (signature
    inspection at trace time — see policies.py) receives the maintained
    global min-wear register.

    While a rescue is pending (``rescue_page >= 0``) policy proposals are
    suppressed and the single DMA channel is offered the rescue migration
    instead: a slow-resident dying page promotes into a CLOCK victim
    frame (consuming the victim from the rotation exactly like a policy
    promotion); a fast-resident dying page swaps with the first healthy
    slow-resident page of this chunk's access stream (the donor parks on
    the dead frame as the tombstone — poison travel in the swap commit).
    Returns ``(dma, clock_ptr)``."""
    any_valid = jnp.any(valid)
    branches = [
        functools.partial(fn, cfg, params, min_wear=min_wear)
        if "min_wear" in inspect.signature(fn).parameters
        else functools.partial(fn, cfg, params)
        for fn in registry.fns]
    ops_ = (table, sc.clock_ptr, page, is_write, valid)
    if len(branches) == 1:
        p_want, cand, victim, new_ptr = branches[0](*ops_)
    else:
        p_want, cand, victim, new_ptr = jax.lax.switch(
            params.policy_id, branches, *ops_)
    # Post-policy proposal mask: device sanity plus FLAGS enforcement — a
    # pinned candidate or victim vetoes the swap no matter what the
    # policy proposed (maybe_start re-checks the same pin bits).
    cand_row, victim_row = table[cand], table[victim]
    unpinned = ~(table_lib.is_pinned(cand_row) |
                 table_lib.is_pinned(victim_row))
    want = p_want & any_valid & unpinned & \
        (table_lib.device(cand_row) == SLOW) & \
        (table_lib.device(victim_row) == FAST)

    # Rescue migration override (exactly no-effect while the register is
    # idle — every committed value reduces to the policy's).
    pending = rescue_page >= 0
    resc = jnp.clip(rescue_page, 0, table.shape[0] - 1)
    r_slow = table_lib.device(table[resc]) == SLOW
    r_victim, r_found, r_skip = policies_lib._clock_victim(
        table, sc.clock_ptr, params.n_fast_pages)
    pg = jnp.clip(page, 0, table.shape[0] - 1)
    rows_pg = table[pg]
    donor_ok = valid & (table_lib.device(rows_pg) == SLOW) & \
        ((table_lib.flags(rows_pg) &
          (table_lib.PINNED | table_lib.RETIRED | table_lib.POISONED)) == 0)
    dj = jnp.argmax(donor_ok)
    r_want = pending & jnp.where(r_slow, r_found, donor_ok[dj])
    final_want = jnp.where(pending, r_want, want)
    page_a = jnp.where(pending, jnp.where(r_slow, resc, pg[dj]), cand)
    page_b = jnp.where(pending, jnp.where(r_slow, r_victim, resc), victim)

    dma, started = dma_lib.maybe_start(dma, final_want, page_a, page_b, now,
                                       table)
    # CLOCK pointer commit (two cases, see policies.py): a proposal only
    # consumes its victim frame when the swap actually started; with no
    # proposal, the policy's pointer motion commits as-is (pin skipping).
    # A started slow-resident rescue consumes its victim the same way; a
    # fast-resident rescue touches no CLOCK frame. While a rescue is
    # merely pending (engine busy, no donor yet) the pointer holds — the
    # suppressed policy proposal consumed nothing.
    ptr_rescue = (sc.clock_ptr + r_skip + 1) % params.n_fast_pages
    clock_ptr = jnp.where(
        pending,
        jnp.where(r_slow & started, ptr_rescue, sc.clock_ptr),
        jnp.where(started | ~p_want, new_ptr, sc.clock_ptr))
    return dma, clock_ptr


# --------------------------------------------------------------------------- #
# the whole step: ref composition
# --------------------------------------------------------------------------- #

def step_ref(cfg: EmulatorConfig, registry: PolicyRegistry, table: jax.Array,
             params: RuntimeParams, sc: StepScalars, bank_free: jax.Array,
             page, offset, is_write, size, valid,
             faults: faults_lib.FaultPlan | None = None, *,
             seq: bool = False):
    """One chunk end-to-end (reads -> commit -> retire -> policy). The
    jnp reference AND the scan path; ``seq=True`` is the same step with
    the sequential in-kernel recurrences (what the Pallas body runs).
    ``faults`` defaults to the empty plan (bitwise no-op).

    Each phase runs under a named scope (``hmmu.pipeline``,
    ``hmmu.commit``, ``hmmu.retire``, ``hmmu.policy``) that XLA keeps in
    every operation's ``op_name`` metadata, so a device trace attributes
    each operation's time to its phase and stage. Scopes are metadata
    only: the arithmetic is the same with or without them.

    Returns ``(table, scalars, bank_free, outs)`` with ``outs`` carrying
    per-request results (``returns`` masked, ``device`` raw post-redirect,
    ``latency`` masked), the ``held``/``poisoned``/``injected`` counter
    inputs, and the boundary's ``retired``/``tombstone`` page scalars
    (-1 when none).
    """
    if faults is None:
        faults = faults_lib.FaultPlan.empty()
    with jax.named_scope("hmmu.pipeline"):
        pipe = pipeline_phase(cfg, params, table, sc, bank_free,
                              page, offset, is_write, size, valid, seq=seq)
        # Transient fault injection: purely observational — the access
        # completes (the emulated device returned corrupt data); the
        # serving layer refetches.
        tc, tp = faults.transient[:, 0], faults.transient[:, 1]
        injected = ((page[:, None] == tp[None, :]) &
                    (tc[None, :] == sc.chunk_idx)).any(axis=1) & valid
    with jax.named_scope("hmmu.commit"):
        table, dma, done, now, last_ret, min_wear, tombstone = commit_phase(
            cfg, params, table, sc, pipe, page, is_write, valid,
            eff_write_weight(params, registry))
    with jax.named_scope("hmmu.retire"):
        rescue_page = jnp.where(done & (tombstone >= 0), -1,
                                jnp.asarray(sc.rescue_page, jnp.int32))
        table, rescue_page, fault_cursor, retired = retire_phase(
            cfg, params, table, sc, rescue_page,
            jnp.asarray(sc.fault_cursor, jnp.int32), faults, page, valid)
    with jax.named_scope("hmmu.policy"):
        dma, clock_ptr = policy_phase(cfg, params, registry, table, sc, dma,
                                      now, page, is_write, valid,
                                      rescue_page, min_wear)
    any_valid = jnp.any(valid)
    sc2 = StepScalars(
        clock=now, clock_ptr=clock_ptr, chunk_idx=sc.chunk_idx + 1, dma=dma,
        link_free_rx=jnp.where(any_valid, pipe.rx_last, sc.link_free_rx),
        link_free_tx=jnp.where(any_valid, pipe.tx_last, sc.link_free_tx),
        last_return=last_ret, rescue_page=rescue_page,
        min_wear=jnp.asarray(min_wear, jnp.int32), fault_cursor=fault_cursor)
    outs = {"returns": jnp.where(valid, pipe.returns, 0),
            "device": pipe.dev, "latency": pipe.lat,
            "held": pipe.held, "poisoned": pipe.poisoned,
            "injected": injected, "retired": retired,
            "tombstone": jnp.asarray(tombstone, jnp.int32)}
    return table, sc2, pipe.bank_free, outs


# --------------------------------------------------------------------------- #
# the Pallas path
# --------------------------------------------------------------------------- #

# RuntimeParams fields carried as float32 in the kernel's float operand;
# everything else rides the int32 scalar-prefetch vector. Must agree with
# RuntimeParams.from_config dtypes (asserted by the kernel test suite).
_FLOAT_PARAM_FIELDS = frozenset({
    "fast_bytes_per_cycle", "slow_bytes_per_cycle", "link_bytes_per_cycle",
    "pin_fast_fraction", "power_pj_per_bit_fast",
    "power_pj_per_bit_slow_read", "power_pj_per_bit_slow_write"})

# Scalar-state slots at the head of the int vector (before int params).
_N_SC = 14


def _pack_scalars(params: RuntimeParams, sc: StepScalars):
    """(int32[NI], float32[NF]): 14 state scalars + int params, and the
    float params. ``policy_id`` rides the int vector — that is the
    scalar-prefetched dispatch operand."""
    ints = [sc.clock, sc.clock_ptr, sc.chunk_idx, sc.dma.active,
            sc.dma.page_a, sc.dma.page_b, sc.dma.start, sc.dma.swaps_done,
            sc.link_free_rx, sc.link_free_tx, sc.last_return,
            sc.rescue_page, sc.min_wear, sc.fault_cursor]
    floats = []
    for name, v in zip(RuntimeParams._fields, params):
        (floats if name in _FLOAT_PARAM_FIELDS else ints).append(v)
    return (jnp.stack([jnp.asarray(v, jnp.int32) for v in ints]),
            jnp.stack([jnp.asarray(v, jnp.float32) for v in floats]))


def _unpack_scalars(ints: jax.Array, floats: jax.Array):
    """Inverse of :func:`_pack_scalars` (inside the kernel body)."""
    sc = StepScalars(
        clock=ints[0], clock_ptr=ints[1], chunk_idx=ints[2],
        dma=dma_lib.DMAState(active=ints[3], page_a=ints[4], page_b=ints[5],
                             start=ints[6], swaps_done=ints[7]),
        link_free_rx=ints[8], link_free_tx=ints[9], last_return=ints[10],
        rescue_page=ints[11], min_wear=ints[12], fault_cursor=ints[13])
    vals, ii, fi = {}, _N_SC, 0
    for name in RuntimeParams._fields:
        if name in _FLOAT_PARAM_FIELDS:
            vals[name] = floats[fi]
            fi += 1
        else:
            vals[name] = ints[ii]
            ii += 1
    return RuntimeParams(**vals), sc


@functools.lru_cache(maxsize=None)
def _pallas_step_fn(cfg: EmulatorConfig, registry: PolicyRegistry,
                    interpret: bool):
    """Build (and cache) the batched one-kernel step for one static
    geometry + frozen registry. The returned function takes/returns
    arrays with an arbitrary leading batch shape; its ``custom_vmap``
    rule maps a vmapped sweep's design-point axis onto the kernel's grid,
    so all points launch once per chunk."""

    def _body(ints_ref, table_ref, page_ref, offset_ref, iw_ref, size_ref,
              valid_ref, floats_ref, bank_free_ref, transient_ref,
              deaths_ref,
              out_table_ref, out_sc_ref, out_bank_ref,
              out_ret_ref, out_dev_ref, out_lat_ref, out_poi_ref,
              out_inj_ref):
        bi = pl.program_id(0)
        params, sc = _unpack_scalars(ints_ref[bi], floats_ref[0])
        faults = faults_lib.FaultPlan(transient=transient_ref[0],
                                      deaths=deaths_ref[0])
        table, sc2, bank_free2, outs = step_ref(
            cfg, registry, table_ref[0], params, sc, bank_free_ref[0],
            page_ref[0], offset_ref[0], iw_ref[0] != 0, size_ref[0],
            valid_ref[0] != 0, faults, seq=True)
        out_table_ref[0] = table
        out_sc_ref[0] = jnp.stack(
            [sc2.clock, sc2.clock_ptr, sc2.chunk_idx, sc2.dma.active,
             sc2.dma.page_a, sc2.dma.page_b, sc2.dma.start,
             sc2.dma.swaps_done, sc2.link_free_rx, sc2.link_free_tx,
             sc2.last_return, sc2.rescue_page, sc2.min_wear,
             sc2.fault_cursor, outs["held"], outs["retired"],
             outs["tombstone"]])
        out_bank_ref[0] = bank_free2
        out_ret_ref[0] = outs["returns"]
        out_dev_ref[0] = outs["device"]
        out_lat_ref[0] = outs["latency"]
        out_poi_ref[0] = outs["poisoned"].astype(jnp.int32)
        out_inj_ref[0] = outs["injected"].astype(jnp.int32)

    @custom_batching.custom_vmap
    def step(table, page, offset, is_write, size, valid, ints, floats,
             bank_free, transient, deaths):
        batch = table.shape[:-2]
        n_pages, w = table.shape[-2:]
        chunk = page.shape[-1]
        ni = ints.shape[-1]
        nf = floats.shape[-1]
        nb = bank_free.shape[-1]
        nt = transient.shape[-2]
        nd = deaths.shape[-2]
        tb = table.reshape(-1, n_pages, w)
        b = tb.shape[0]

        def vec(x):
            return x.reshape(b, -1)

        def spec(*shape):
            return pl.BlockSpec((1, *shape),
                                lambda bi, ints: (bi,) + (0,) * len(shape))

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[spec(n_pages, w), spec(chunk), spec(chunk),
                      spec(chunk), spec(chunk), spec(chunk), spec(nf),
                      spec(nb), spec(nt, 2), spec(nd, 2)],
            out_specs=[spec(n_pages, w), spec(_N_SC + 3), spec(nb),
                       spec(chunk), spec(chunk), spec(chunk), spec(chunk),
                       spec(chunk)],
        )
        i32 = jnp.int32
        outs = pl.pallas_call(
            _body,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, n_pages, w), i32),
                jax.ShapeDtypeStruct((b, _N_SC + 3), i32),
                jax.ShapeDtypeStruct((b, nb), i32),
                jax.ShapeDtypeStruct((b, chunk), i32),
                jax.ShapeDtypeStruct((b, chunk), i32),
                jax.ShapeDtypeStruct((b, chunk), i32),
                jax.ShapeDtypeStruct((b, chunk), i32),
                jax.ShapeDtypeStruct((b, chunk), i32),
            ],
            interpret=interpret,
        )(vec(ints), tb, vec(page), vec(offset), vec(is_write), vec(size),
          vec(valid), vec(floats), vec(bank_free),
          transient.reshape(-1, nt, 2), deaths.reshape(-1, nd, 2))
        tbl2, scv, bf2, ret, dev, lat, poi, inj = outs
        return (tbl2.reshape(*batch, n_pages, w),
                scv.reshape(*batch, _N_SC + 3),
                bf2.reshape(*batch, nb),
                ret.reshape(*batch, chunk), dev.reshape(*batch, chunk),
                lat.reshape(*batch, chunk), poi.reshape(*batch, chunk),
                inj.reshape(*batch, chunk))

    @step.def_vmap
    def _step_vmap(axis_size, in_batched, *args):
        # vmap (the sweep's design-point axis) becomes the kernel's
        # leading grid axis: one launch steps every design point's chunk.
        # The sweep batches state + params but shares the trace (and, for
        # a shared fault scenario, the plan), so broadcast whichever
        # operands aren't batched.
        args = tuple(
            a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, b in zip(args, in_batched))
        return step(*args), (True,) * 8

    return step


def use_chunk_step_kernel(cfg: EmulatorConfig) -> bool:
    """Resolve the ``chunk_step_kernel`` knob (static, host-side): "on"
    forces the kernel (interpret mode off-TPU — how CPU tests run it; on
    a TPU the compiler refuses it and the call raises), "off" forces the
    scan path, "auto" takes the kernel only where
    :func:`kernels.ops.use_pallas` selects ``"chunk_step"`` (never by
    default on a TPU, which refuses it; REPRO_FORCE_PALLAS=1 anywhere)
    and the table fits the VMEM budget."""
    knob = cfg.chunk_step_kernel
    if knob == "off":
        return False
    if knob == "on":
        return True
    if knob != "auto":
        raise ValueError(f"unknown chunk_step_kernel {knob!r}; expected "
                         "'auto', 'on' or 'off'")
    return (kernel_ops.use_pallas("chunk_step") and
            cfg.n_pages * table_lib.ROW_W * 4 <= VMEM_TABLE_BUDGET)


def chunk_step(cfg: EmulatorConfig, registry: PolicyRegistry,
               table: jax.Array, params: RuntimeParams, sc: StepScalars,
               bank_free: jax.Array, page, offset, is_write, size, valid,
               faults: faults_lib.FaultPlan | None = None):
    """THE chunk step — one-kernel Pallas path or the scan path, resolved
    by :func:`use_chunk_step_kernel` (bitwise identical either way).
    Signature/returns as :func:`step_ref`."""
    if faults is None:
        faults = faults_lib.FaultPlan.empty()
    if not use_chunk_step_kernel(cfg):
        return step_ref(cfg, registry, table, params, sc, bank_free,
                        page, offset, is_write, size, valid, faults)
    fn = _pallas_step_fn(cfg, registry, kernel_ops._interpret())
    ints, floats = _pack_scalars(params, sc)
    tbl2, scv, bank_free2, returns, dev, lat, poi, inj = fn(
        table, page, offset, is_write.astype(jnp.int32), size,
        valid.astype(jnp.int32), ints, floats, bank_free,
        faults.transient, faults.deaths)
    sc2 = StepScalars(
        clock=scv[0], clock_ptr=scv[1], chunk_idx=scv[2],
        dma=dma_lib.DMAState(active=scv[3], page_a=scv[4], page_b=scv[5],
                             start=scv[6], swaps_done=scv[7]),
        link_free_rx=scv[8], link_free_tx=scv[9], last_return=scv[10],
        rescue_page=scv[11], min_wear=scv[12], fault_cursor=scv[13])
    outs = {"returns": returns, "device": dev, "latency": lat,
            "held": scv[14], "poisoned": poi != 0, "injected": inj != 0,
            "retired": scv[15], "tombstone": scv[16]}
    return tbl2, sc2, bank_free2, outs
