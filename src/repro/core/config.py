"""Configuration for the hybrid-memory emulation platform.

All times are integer *cycles* of the emulated HMMU clock (1 cycle == 1 ns
at the paper's 1 GHz fabric reference), mirroring the paper's stall-cycle
latency-injection mechanism (paper §III-F): technologies are emulated by
scaling cycle counts from the DRAM round trip, not by modelling devices.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Device ids used throughout the platform.
FAST = 0  # "DRAM"  — the fast tier
SLOW = 1  # "NVM"   — the slow tier (emulated technology)


@dataclasses.dataclass(frozen=True)
class TechnologyParams:
    """Per-technology access characteristics (paper Table I).

    read/write latencies in cycles (== ns); bandwidth in bytes/cycle
    (== GB/s at 1 GHz).
    """

    name: str
    read_lat: int
    write_lat: int
    bytes_per_cycle: float
    # Write endurance (cycles of the cell, not clock cycles) — tracked by a
    # counter so wear policies can be studied; no behavioural effect here.
    endurance_log10: float = 16.0


@dataclasses.dataclass(frozen=True)
class EmulatorConfig:
    """Static configuration of the emulation platform (paper Table II)."""

    # --- address space geometry -------------------------------------------------
    page_size: int = 4096           # bytes per page (migration granularity)
    subblock: int = 512             # DMA transfer sub-block (paper §III-D)
    n_fast_pages: int = 32768       # 128 MB DRAM tier  (paper Table II)
    n_slow_pages: int = 262144      # 1 GB NVM tier     (paper Table II)
    line_size: int = 64             # request granularity after cache filtering

    # --- device timing ------------------------------------------------------------
    fast: TechnologyParams = dataclasses.field(
        default_factory=lambda: TECHNOLOGIES["dram"])
    slow: TechnologyParams = dataclasses.field(
        default_factory=lambda: TECHNOLOGIES["3dxpoint"])
    n_banks: int = 16               # banks per device (queue contention model)

    # --- interconnect ("PCIe" in the paper's platform) ----------------------------
    link_lat: int = 600             # per-request link round-trip overhead, cycles.
    #   The paper identifies PCIe latency as the dominant slowdown term for
    #   request-heavy workloads (§IV-B); 600 ns ≈ PCIe Gen3 round trip.
    link_bytes_per_cycle: float = 8.0   # PCIe Gen3 x8 ≈ 8 GB/s

    # --- host issue model ---------------------------------------------------------
    issue_gap: int = 4              # cycles between consecutive requests leaving
    #   the host cache hierarchy (open-loop arrival); chunk boundaries are
    #   closed-loop: the next chunk starts no earlier than the last in-order
    #   return of the previous chunk (host blocks on outstanding reads).
    max_inflight: int = 64          # host MSHR-like cap within a chunk

    # --- DMA engine (paper §III-D) -------------------------------------------------
    dma_bytes_per_cycle: float = 16.0  # dedicated migration engine bandwidth
    dma_buffer_bytes: int = 8192       # internal staging buffer (2 pages)

    # --- emulation pipeline -----------------------------------------------------
    chunk: int = 256                # requests per pipeline chunk (policy-commit
    #   granularity; chunk=1 reproduces a fully sequential model exactly)
    bank_resolver: str = "auto"     # bank-queue resolution algorithm:
    #   "dense"     — one-hot [2*n_banks, chunk] lane matrix, O(n_banks*chunk)
    #                 (the original formulation; kept as the oracle)
    #   "segmented" — stable-sort by bank + segmented max-plus scan,
    #                 O(chunk log chunk) independent of n_banks
    #   "auto"      — pick by geometry (latency.pick_bank_resolver)
    #   Both are bitwise-identical (tests/test_latency_consistency.py).
    fuse_swap_gather: bool = True   # fetch the DMA swap pair's table rows in
    #   the same lookup-kernel launch as the chunk's pages (chunk+2 rows)
    #   instead of two separate dynamic-slice gathers
    scan_unroll: int = 1            # unroll factor of the chunk lax.scan
    chunk_step_kernel: str = "auto"  # one-kernel Pallas chunk step:
    #   "on"   — run the whole per-chunk step (gather, redirect, bank
    #            resolve, in-order return, commit, policy proposal) as ONE
    #            pallas_call with the packed table staged through VMEM
    #            (interpret mode off-TPU, so tests can force it anywhere;
    #            the TPU compiler refuses it, so "on" raises on a TPU)
    #   "off"  — the composable jnp scan path (bitwise identical)
    #   "auto" — the scan path on a TPU (the kernel is in
    #            kernels.ops.REFUSED_ON_TPU); the kernel only where
    #            REPRO_FORCE_PALLAS=1 and the table fits the VMEM budget
    #   Resolution in kernels.chunk_step.use_chunk_step_kernel.

    # --- policy -------------------------------------------------------------------
    policy: str = "hotness"         # one of core.policies.POLICIES
    hot_threshold: int = 8          # accesses before a slow page is promoted
    hotness_decay_shift: int = 1    # hotness >>= shift at each decay boundary
    decay_every: int = 16           # decay every N chunks (hardware aging tick)
    write_weight: int = 1           # extra hotness weight for writes — applied
    #   ONLY by the "write_bias" policy (policy-scoped; other policies weight
    #   reads and writes equally so a policy-axis sweep actually compares)
    wear_slack: int = 64            # "wear_level" destination tolerance: slow
    #   frames worn more than (chunk minimum + slack) writes are skipped as
    #   demotion destinations (one full-page migration = page_size/line_size
    #   = 64 line-writes with the default geometry)
    pin_fast_fraction: float = 0.0  # fraction of the fast tier pinned
    #   (FLAGS |= PIN_FAST) at init — pages the paper's §III-G malloc hints
    #   nail to DRAM; pinned frames are never CLOCK victims
    endurance_budget: int = 0       # frame retirement threshold in WEAR-lane
    #   line-writes: when a slow frame's WEAR crosses the budget at a chunk
    #   boundary, the frame is retired — its resident page is POISONED and a
    #   rescue migration remaps it to a healthy frame (core.faults has the
    #   fault-injection companion). <= 0 disables retirement entirely (the
    #   default: runs are bitwise-identical to the pre-retirement emulator)

    # --- misc ----------------------------------------------------------------------
    power_pj_per_bit_fast: float = 1.2   # dynamic-power estimate coefficients
    power_pj_per_bit_slow_read: float = 2.0
    power_pj_per_bit_slow_write: float = 12.0

    @property
    def n_pages(self) -> int:
        return self.n_fast_pages + self.n_slow_pages

    @property
    def subblocks_per_page(self) -> int:
        return self.page_size // self.subblock

    @property
    def dma_cycles_per_subblock(self) -> int:
        return max(1, round(self.subblock / self.dma_bytes_per_cycle))

    def with_(self, **kw) -> "EmulatorConfig":
        return dataclasses.replace(self, **kw)

    def runtime(self) -> "RuntimeParams":
        return RuntimeParams.from_config(self)


def static_key(cfg: EmulatorConfig) -> tuple:
    """The fields of ``cfg`` that determine compiled shapes and program
    structure. Two configs with equal ``static_key`` share every compiled
    emulation program — this tuple is the leading component of the
    session API's unified entry-point cache key (``repro.Engine``; two
    same-geometry Engines reuse each other's executables). Everything
    else lives in ``RuntimeParams`` and is traced.

    Note the *total* page count is static but the fast/slow split is not:
    the redirection table is initialized from a traced boundary, so tier
    ratios are a batchable design axis.
    """
    return (cfg.page_size, cfg.subblock, cfg.n_pages, cfg.line_size,
            cfg.n_banks, cfg.chunk, cfg.max_inflight, cfg.dma_buffer_bytes,
            cfg.bank_resolver, cfg.fuse_swap_gather, cfg.scan_unroll,
            cfg.chunk_step_kernel)


def canonical_config(cfg: EmulatorConfig) -> EmulatorConfig:
    """A representative config carrying only ``cfg``'s static fields, with
    every runtime field left at its class default. Configs with equal
    :func:`static_key` canonicalize identically, so jit caches keyed on
    the canonical config are shared across sweeps that differ only in
    runtime parameters. Only meaningful where ``params`` is always
    supplied explicitly (the sweep executor) — the runtime defaults of
    the result are arbitrary."""
    return EmulatorConfig(
        page_size=cfg.page_size, subblock=cfg.subblock,
        n_fast_pages=1, n_slow_pages=cfg.n_pages - 1,
        line_size=cfg.line_size, n_banks=cfg.n_banks, chunk=cfg.chunk,
        max_inflight=cfg.max_inflight, dma_buffer_bytes=cfg.dma_buffer_bytes,
        bank_resolver=cfg.bank_resolver,
        fuse_swap_gather=cfg.fuse_swap_gather, scan_unroll=cfg.scan_unroll,
        chunk_step_kernel=cfg.chunk_step_kernel)


class RuntimeParams(NamedTuple):
    """Traced runtime parameters of the platform — a JAX pytree.

    Everything the emulation pipeline reads per design point (technology
    timings, bandwidths, link/issue timing, policy knobs, the fast-tier
    boundary, the policy selector) lives here as a scalar array, so
    the emulation program compiles once per :func:`static_key` and any number of
    design points run through the same XLA computation — vmapping over a
    stacked ``RuntimeParams`` batch is the sweep engine's core mechanism.

    Field names deliberately mirror ``EmulatorConfig`` (flattened for the
    two ``TechnologyParams``), so helpers that only touch shared fields
    accept either object.
    """

    # device timing (cfg.fast / cfg.slow, flattened)
    fast_read_lat: jax.Array       # int32 cycles
    fast_write_lat: jax.Array
    fast_bytes_per_cycle: jax.Array  # float32
    slow_read_lat: jax.Array
    slow_write_lat: jax.Array
    slow_bytes_per_cycle: jax.Array
    # interconnect + host issue model
    link_lat: jax.Array            # int32
    link_bytes_per_cycle: jax.Array  # float32
    issue_gap: jax.Array           # int32
    # DMA engine bandwidth (pre-divided: cycles per 512B sub-block move)
    dma_cycles_per_subblock: jax.Array  # int32
    # tier geometry: fast/slow boundary within the static n_pages space
    n_fast_pages: jax.Array        # int32
    # policy knobs + selector (index into policies.POLICIES order)
    hot_threshold: jax.Array       # int32
    hotness_decay_shift: jax.Array
    decay_every: jax.Array
    write_weight: jax.Array
    wear_slack: jax.Array          # int32 — wear_level destination tolerance
    pin_fast_fraction: jax.Array   # float32 — fast-tier share pinned at init
    endurance_budget: jax.Array    # int32 — frame retirement threshold
    #   (<= 0 disables retirement; see EmulatorConfig.endurance_budget)
    policy_id: jax.Array
    # power model coefficients
    power_pj_per_bit_fast: jax.Array        # float32
    power_pj_per_bit_slow_read: jax.Array
    power_pj_per_bit_slow_write: jax.Array

    @classmethod
    def from_config(cls, cfg: EmulatorConfig) -> "RuntimeParams":
        from . import policies  # deferred; policies imports this module
        i32, f32 = jnp.int32, jnp.float32
        return cls(
            fast_read_lat=i32(cfg.fast.read_lat),
            fast_write_lat=i32(cfg.fast.write_lat),
            fast_bytes_per_cycle=f32(cfg.fast.bytes_per_cycle),
            slow_read_lat=i32(cfg.slow.read_lat),
            slow_write_lat=i32(cfg.slow.write_lat),
            slow_bytes_per_cycle=f32(cfg.slow.bytes_per_cycle),
            link_lat=i32(cfg.link_lat),
            link_bytes_per_cycle=f32(cfg.link_bytes_per_cycle),
            issue_gap=i32(cfg.issue_gap),
            dma_cycles_per_subblock=i32(cfg.dma_cycles_per_subblock),
            n_fast_pages=i32(cfg.n_fast_pages),
            hot_threshold=i32(cfg.hot_threshold),
            hotness_decay_shift=i32(cfg.hotness_decay_shift),
            decay_every=i32(cfg.decay_every),
            write_weight=i32(cfg.write_weight),
            wear_slack=i32(cfg.wear_slack),
            pin_fast_fraction=f32(cfg.pin_fast_fraction),
            endurance_budget=i32(cfg.endurance_budget),
            policy_id=i32(policies.policy_id(cfg.policy)),
            power_pj_per_bit_fast=f32(cfg.power_pj_per_bit_fast),
            power_pj_per_bit_slow_read=f32(cfg.power_pj_per_bit_slow_read),
            power_pj_per_bit_slow_write=f32(cfg.power_pj_per_bit_slow_write),
        )

    def with_(self, **kw) -> "RuntimeParams":
        return self._replace(**kw)


# Paper Table I, converted to cycles (ns) and bytes/cycle. Bandwidths are
# platform-level defaults (a DDR4 DIMM, Optane-class media, ...), since
# Table I only gives latencies; all are overridable per experiment.
TECHNOLOGIES: dict[str, TechnologyParams] = {
    "dram": TechnologyParams("dram", read_lat=50, write_lat=50,
                             bytes_per_cycle=19.2, endurance_log10=16),
    "3dxpoint": TechnologyParams("3dxpoint", read_lat=100, write_lat=275,
                                 bytes_per_cycle=2.4, endurance_log10=9),
    "stt-ram": TechnologyParams("stt-ram", read_lat=20, write_lat=20,
                                bytes_per_cycle=12.8, endurance_log10=16),
    "mram": TechnologyParams("mram", read_lat=20, write_lat=20,
                             bytes_per_cycle=12.8, endurance_log10=15),
    "flash": TechnologyParams("flash", read_lat=100_000, write_lat=100_000,
                              bytes_per_cycle=0.5, endurance_log10=4),
    # "hdd" from Table I is out of scope for a memory bus (5 ms) but kept for
    # completeness of the technology table.
    "hdd": TechnologyParams("hdd", read_lat=5_000_000, write_lat=5_000_000,
                            bytes_per_cycle=0.15, endurance_log10=15),
}


def paper_platform() -> EmulatorConfig:
    """The exact platform of paper Table II: 128 MB DRAM + 1 GB emulated
    3D XPoint behind a PCIe Gen3 link."""
    return EmulatorConfig()


def small_platform(**kw) -> EmulatorConfig:
    """A reduced platform for tests: tiny page counts, small chunks."""
    base = dict(n_fast_pages=8, n_slow_pages=56, chunk=16, hot_threshold=3)
    base.update(kw)
    return EmulatorConfig(**base)
