"""The HMMU emulation pipeline — the platform's "FPGA fabric".

Requests flow through the same stages as the paper's Fig 2 workflow:

    RX link -> TLP decode -> redirection-table lookup -> DMA-conflict
    redirect -> bank queues (per device) -> media access -> tag-match
    in-order return -> TX link

Each stage is a vectorized array computation over a *chunk* of requests;
ordering-sensitive stages (bank queues, link serialization, in-order
return) are resolved exactly with associative scans (see latency.py,
consistency.py). Policy state (hotness, migrations) commits at chunk
boundaries — the pipeline-depth visibility delay real RTL has.

The chunk step itself lives in ``repro.kernels.chunk_step`` — ONE fused
step (Pallas kernel with the packed table in VMEM, or the bitwise-
identical jnp scan path) covering all five pipeline stages plus the
boundary commit and the policy proposal. That module documents the
authoritative read-before-write chunk schedule; this one just scans it
over the trace and accumulates counters.

``chunk=1`` degrades to a fully sequential model, which the oracle tests
compare against; large chunks are the "FPGA mode" delivering the paper's
orders-of-magnitude speedup over sequential software simulation.

**Drive the platform through the session API.** ``repro.Engine``
(``repro/engine.py``) is the public entry point: it owns the static
geometry, a frozen :class:`~repro.core.policies.PolicyRegistry`, and the
unified jit entry-point cache below (:func:`entry_point`), and exposes
``run`` / ``run_stream`` / ``run_channels`` / ``sweep`` /
``continue_sweep``.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import counters as counters_lib, dma as dma_lib, table as table_lib
from .config import EmulatorConfig, RuntimeParams, static_key
from .faults import FaultPlan
from .policies import PolicyRegistry
from repro.kernels import chunk_step as chunk_step_lib


class Trace(NamedTuple):
    """A memory-request trace (struct-of-arrays, int32)."""
    page: jax.Array      # flat page number
    offset: jax.Array    # byte offset within the page
    is_write: jax.Array  # bool
    size: jax.Array      # bytes (usually the 64B line size)

    def __len__(self):
        return self.page.shape[-1]


class EmulatorState(NamedTuple):
    table: jax.Array          # int32[n_pages, table.ROW_W] — the packed
    #   per-page metadata store (core.table): redirection mapping
    #   (DEVICE/FRAME lanes), policy hotness, NVM wear histogram (WEAR
    #   lane keyed by slow frame — the endurance row of paper Table I;
    #   policies like write_bias exist to flatten exactly this histogram),
    #   the CLOCK inverse map (OWNER lane keyed by fast frame), and the
    #   last-migration EPOCH stamp. Same row format the Pallas lookup
    #   kernel serves on the hot path.
    clock_ptr: jax.Array      # int32 — CLOCK victim pointer over fast frames
    chunk_idx: jax.Array      # int32 — chunks processed (decay ticks)
    dma: dma_lib.DMAState
    clock: jax.Array          # int32 cycles
    bank_free: jax.Array      # int32[2 * n_banks] — per device x bank
    link_free_rx: jax.Array   # int32
    link_free_tx: jax.Array   # int32
    last_return: jax.Array    # int32
    counters: counters_lib.Counters
    rescue_page: jax.Array    # int32 — page awaiting rescue off a dead
    #   frame (-1 when idle); at most one rescue is in flight at a time
    #   (kernels.chunk_step.retire_phase documents the lifecycle)
    min_wear: jax.Array       # int32 — global min slow-frame WEAR,
    #   rescrubbed at decay boundaries (wear_level's slack reference)
    fault_cursor: jax.Array   # int32 — next unconsumed FaultPlan death


def init_state(cfg: EmulatorConfig,
               params: RuntimeParams | None = None) -> EmulatorState:
    """Fresh platform state. The table's WEAR and OWNER lanes are sized by
    the static total page count (the fast/slow split is a runtime
    parameter); rows beyond the active tier are never read. A nonzero
    ``pin_fast_fraction`` (config or params) pre-pins that share of the
    fast tier via the FLAGS lane."""
    nf = None if params is None else params.n_fast_pages
    pin = None if params is None else params.pin_fast_fraction
    z = jnp.int32(0)
    return EmulatorState(
        table=table_lib.init_table(cfg, nf, pin),
        clock_ptr=z, chunk_idx=z,
        dma=dma_lib.DMAState.idle(),
        clock=z,
        bank_free=jnp.zeros(2 * cfg.n_banks, jnp.int32),
        link_free_rx=z, link_free_tx=z, last_return=z,
        counters=counters_lib.Counters.zeros(),
        rescue_page=jnp.int32(-1), min_wear=z, fault_cursor=z,
    )


def pad_trace(cfg: EmulatorConfig, t: Trace) -> tuple[Trace, jax.Array]:
    """Pad to a multiple of cfg.chunk; returns (trace, valid mask)."""
    n = len(t)
    rem = (-n) % cfg.chunk
    valid = jnp.arange(n + rem) < n
    if rem:
        t = Trace(*(jnp.pad(x, (0, rem)) for x in t))
    return t, valid


def _chunk_step(cfg: EmulatorConfig, params: RuntimeParams,
                registry: PolicyRegistry, faults: FaultPlan,
                state: EmulatorState, chunk: tuple[Trace, jax.Array]):
    """One scan step = one chunk through the fused step.

    The five pipeline stages (RX link -> lookup/redirect -> bank queues ->
    in-order return -> TX link), the boundary commit, and the policy
    proposal all execute inside ``kernels.chunk_step`` — as one Pallas
    kernel or the bitwise-identical scan path, per the
    ``cfg.chunk_step_kernel`` knob. That module's docstring is the
    authoritative statement of the chunk's read/write schedule (all table
    reads against the pre-chunk table; ONE combined boundary scatter; the
    policy reads the committed table). Here we only split state into the
    kernel's carry (scalars + table + bank_free), step it, and fold the
    chunk's results into the float counter accumulators — which stay
    outside the kernel, int32-in float32-out — under the named scope
    ``hmmu.counters``.
    """
    trace, valid = chunk
    page, offset, is_write, size = trace
    size = jnp.where(valid, size, 0)
    sc = chunk_step_lib.StepScalars(
        clock=state.clock, clock_ptr=state.clock_ptr,
        chunk_idx=state.chunk_idx, dma=state.dma,
        link_free_rx=state.link_free_rx, link_free_tx=state.link_free_tx,
        last_return=state.last_return, rescue_page=state.rescue_page,
        min_wear=state.min_wear, fault_cursor=state.fault_cursor)
    table, sc, bank_free, outs = chunk_step_lib.chunk_step(
        cfg, registry, state.table, params, sc, state.bank_free,
        page, offset, is_write, size, valid, faults)
    with jax.named_scope("hmmu.counters"):
        ctr = counters_lib.update(
            params, state.counters, device=outs["device"],
            is_write=is_write, size=size, valid=valid,
            latency=outs["latency"], held=outs["held"],
            poisoned=outs["poisoned"], retired=outs["retired"] >= 0,
            injected=outs["injected"])
    new_state = EmulatorState(
        table=table, clock_ptr=sc.clock_ptr, chunk_idx=sc.chunk_idx,
        dma=sc.dma, clock=sc.clock, bank_free=bank_free,
        link_free_rx=sc.link_free_rx, link_free_tx=sc.link_free_tx,
        last_return=sc.last_return, counters=ctr,
        rescue_page=sc.rescue_page, min_wear=sc.min_wear,
        fault_cursor=sc.fault_cursor)
    n = page.shape[0]
    # The boundary's retired/tombstone page scalars broadcast to the
    # chunk's request positions so the scan's stacked outputs reshape to
    # the flat trace like everything else; harvesters take unique >= 0.
    out = {"returns": outs["returns"],
           "device": jnp.where(valid, outs["device"], -1),
           "latency": outs["latency"],
           "faulted": (outs["poisoned"] | outs["injected"]) & valid,
           "retired_page": jnp.full((n,), 1, jnp.int32) * outs["retired"],
           "tombstone": jnp.full((n,), 1, jnp.int32) * outs["tombstone"]}
    return new_state, out


def _emulate_impl(cfg: EmulatorConfig, registry: PolicyRegistry, trace: Trace,
                  valid: jax.Array | None = None,
                  state: EmulatorState | None = None,
                  params: RuntimeParams | None = None,
                  faults: FaultPlan | None = None
                  ) -> tuple[EmulatorState, dict]:
    if params is None:
        params = RuntimeParams.from_config(cfg)
    if faults is None:
        faults = FaultPlan.empty()
    n = len(trace)
    assert n % cfg.chunk == 0, "pad the trace to a chunk multiple first"
    if valid is None:
        valid = jnp.ones(n, bool)
    if state is None:
        state = init_state(cfg, params)
    chunks = jax.tree.map(lambda x: x.reshape(n // cfg.chunk, cfg.chunk),
                          (trace, valid))
    state, outs = jax.lax.scan(
        functools.partial(_chunk_step, cfg, params, registry, faults), state,
        chunks, unroll=cfg.scan_unroll)
    outs = jax.tree.map(lambda x: x.reshape(n), outs)
    return state, outs


def _emulate_batch_impl(cfg: EmulatorConfig, registry: PolicyRegistry,
                        trace: Trace, valid: jax.Array,
                        states, params: RuntimeParams,
                        faults: FaultPlan | None = None):
    """The sweep executor's computation: :func:`_emulate_impl` vmapped over
    a stacked ``RuntimeParams`` batch. ``states`` is an optional stacked
    ``EmulatorState`` with the same leading point axis (a previous
    ``SweepResult.states``) — fresh per-point state when None. ``faults``
    is either one shared plan (broadcast to every point) or a stacked
    per-point batch (``FaultPlan.is_batched`` — failure rate as a design
    axis). Argument order matches ``_emulate_impl`` so one
    ``donate_argnums`` spec serves both entry points."""
    if faults is None:
        faults = FaultPlan.empty()
    f_ax = 0 if faults.is_batched else None
    if states is None:
        def one(p, f):
            return _emulate_impl(cfg, registry, trace, valid, None, p, f)

        return jax.vmap(one, in_axes=(0, f_ax))(params, faults)

    def one(s, p, f):
        return _emulate_impl(cfg, registry, trace, valid, s, p, f)

    return jax.vmap(one, in_axes=(0, 0, f_ax))(states, params, faults)


# ---------------------------------------------------------------------------
# The unified jit entry-point cache.
#
# One cache subsumes the four hand-rolled jit variants this repo used to
# carry (_emulate / _emulate_donated / _emulate_batch /
# _emulate_batch_donated): every compiled emulation program — single run
# or vmapped sweep, donated or not, sharded or not — is one entry, keyed
# by (static geometry, frozen policy registry, batch?, donate?, shape
# signature). The key captures everything that forces a distinct
# executable, so ``entry_cache_count`` IS the compile count (what
# ``Engine.compile_count`` reports) with no reaching into jit internals,
# and a new same-geometry ``Engine`` reuses cached executables for free.
# ---------------------------------------------------------------------------
_ENTRY_CACHE: dict[tuple, Callable] = {}


def entry_point(cfg: EmulatorConfig, registry: PolicyRegistry, *,
                batch: bool = False, donate: bool = False,
                shape_sig: tuple = ()) -> Callable:
    """The compiled entry point for one program shape.

    ``cfg`` must already be canonical (:func:`config.canonical_config`) so
    geometry-equal sessions share entries. ``shape_sig`` carries the
    remaining executable determinants (trace length, point count,
    fresh-vs-carried state, mesh) — callers pass exactly what they are
    about to trace with, keeping one compiled executable per cache entry.

    ``donate=True`` donates the carried state (argument 4 of either
    impl), letting XLA alias its buffers into the outputs: a continued
    emulation updates the packed table in place instead of copying
    n_pages * ROW_W ints every call. The caller's state is CONSUMED.
    """
    key = (static_key(cfg), registry, batch, donate, shape_sig)
    fn = _ENTRY_CACHE.get(key)
    if fn is None:
        impl = _emulate_batch_impl if batch else _emulate_impl
        fn = jax.jit(impl, static_argnames=("cfg", "registry"),
                     donate_argnums=(4,) if donate else ())
        _ENTRY_CACHE[key] = fn
    return fn


def entry_cache_count(skey: tuple | None = None) -> int:
    """Number of compiled emulation entry points — all geometries, or one
    (``skey`` from :func:`config.static_key`). Backs
    ``Engine.compile_count``."""
    if skey is None:
        return len(_ENTRY_CACHE)
    return sum(1 for k in _ENTRY_CACHE if k[0] == skey)


def as_registry(registry) -> PolicyRegistry:
    """Normalize ``None`` / a tuple of names / a ``PolicyRegistry`` into a
    frozen snapshot (``None`` = every registered policy, in registration
    order, snapshotted now)."""
    if isinstance(registry, PolicyRegistry):
        return registry
    return PolicyRegistry.snapshot(registry)
