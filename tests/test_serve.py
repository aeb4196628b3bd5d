"""Property tests for the continuous-batching serving front-end.

The scheduler's contracts, in test form:

* every dispatched batch shape comes from the bucket list, and the
  in-flight dispatch count never exceeds ``max_live_batches``;
* ``Engine.compile_count`` stays flat after ``warmup()`` across a
  mixed-length workload (bucketed shapes + valid-as-argument padding);
* a scheduled run is **bitwise identical** to the same request stream
  replayed serially through ``Engine.run_stream`` — and invariant to
  the async overlap depth;
* pin contracts are stamped at admission, keep the packed table valid
  (``check_table``) mid-run, and are all released by completion;
* eviction under memory pressure never takes a contracted page, and
  evicted window pages are refetched on next use.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro import Engine
from repro.analysis import assert_compile_flat
from repro.core import check_table, small_platform
from repro.core import table as table_lib
from repro.serve import (BucketSpec, ContinuousBatchingScheduler, PagedKVMap,
                         ServeConfig, release_pin_pages, stamp_pin_pages)


def _platform(**kw):
    base = dict(n_fast_pages=64, n_slow_pages=448, chunk=32)
    base.update(kw)
    return small_platform(**base)


def _serve_cfg(**kw):
    base = dict(sorted_batch_sizes=(32, 64, 128), max_live_seqs=100,
                max_admit_per_step=32, max_pages_per_seq=6,
                positions_per_page=8, window_pages=2,
                prefill_writes_per_page=2)
    base.update(kw)
    return ServeConfig(**base)


def _workload(n, seed=0, pmax=4):
    rng = np.random.default_rng(seed)
    return rng.integers(1, pmax, n), rng.integers(1, 16, n)


def _run(engine_cfg, serve_cfg, n_seqs=150, seed=0):
    engine = Engine(engine_cfg)
    sched = ContinuousBatchingScheduler(engine, serve_cfg)
    sched.warmup()
    sched.submit(*_workload(n_seqs, seed))
    sched.run()
    return engine, sched


# ---------------------------------------------------------------------------
# BucketSpec
# ---------------------------------------------------------------------------
def test_bucket_spec_selection():
    b = BucketSpec((32, 64, 256), chunk=32)
    assert b.get_padded_batch_size(1) == 32
    assert b.get_padded_batch_size(33) == 64
    assert b.get_padded_batch_size(256) == 256
    with pytest.raises(ValueError, match="exceed the largest bucket"):
        b.get_padded_batch_size(257)
    assert b.get_dispatch_size(31) is None
    assert b.get_dispatch_size(63) == 32
    assert b.get_dispatch_size(300) == 256


def test_bucket_spec_validation():
    with pytest.raises(ValueError, match="ascending"):
        BucketSpec((64, 32), chunk=32)
    with pytest.raises(ValueError, match="multiple of the pipeline chunk"):
        BucketSpec((48,), chunk=32)
    with pytest.raises(ValueError, match="at least one"):
        BucketSpec((), chunk=32)


# ---------------------------------------------------------------------------
# scheduler properties
# ---------------------------------------------------------------------------
def test_dispatch_shapes_and_admission_cap():
    cfg = _platform()
    engine, sched = _run(cfg, _serve_cfg(max_live_batches=3))
    rep = sched.report()
    assert rep.n_sequences == 150
    sizes = {s for s, _ in sched.dispatch_log}
    assert sizes <= {32, 64, 128}
    assert rep.inflight_high_water <= 3
    assert rep.live_seqs_high_water <= 100


def test_compile_count_flat_after_warmup():
    cfg = _platform()
    engine = Engine(cfg)
    sched = ContinuousBatchingScheduler(engine, _serve_cfg())
    sched.warmup()
    # Mixed lengths: short/long prompts, short/long decodes — every
    # dispatch (steady floor-bucket AND padded drain tail) must hit a
    # warm entry; the valid mask is an argument, not a cache key.
    with assert_compile_flat(engine, msg="serving dispatch after warmup"):
        sched.submit(*_workload(140, seed=3))
        sched.run()
    assert any(n < s for s, n in sched.dispatch_log), \
        "workload never exercised the padded drain path"


def test_scheduled_run_bitwise_equals_run_stream_replay():
    cfg = _platform()
    # pin_pages_per_seq=0: FLAGS ops absent, so the replayed engine sees
    # the identical program stream (smallest bucket == chunk makes the
    # drain padding match run_stream's pad_trace exactly).
    engine, sched = _run(cfg, _serve_cfg(pin_pages_per_seq=0,
                                         record_traces=True), n_seqs=120)
    replay = Engine(cfg).run_stream(iter(sched.trace_log))
    got = {k: np.concatenate([np.asarray(o[k]) for o in sched.outs_log])
           for k in sched.outs_log[0]}
    for k, v in got.items():
        assert np.array_equal(v, np.asarray(replay.outs[k])), k
    for a, b in zip(jax.tree.leaves(sched.carry),
                    jax.tree.leaves(replay.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_results_invariant_to_overlap_depth():
    cfg = _platform()
    reports = []
    for depth in (1, 3):
        _, sched = _run(cfg, _serve_cfg(max_live_batches=depth), n_seqs=120)
        reports.append(sched.report())
    a, b = reports
    assert a.p50_latency_us == b.p50_latency_us
    assert a.p99_latency_us == b.p99_latency_us
    assert a.pinned_fast_hit_rate == b.pinned_fast_hit_rate
    assert a.n_mem_requests == b.n_mem_requests
    assert b.inflight_high_water == 3 > a.inflight_high_water == 1


def test_pin_contracts_stamped_and_released():
    cfg = _platform()
    engine = Engine(cfg)
    sched = ContinuousBatchingScheduler(engine, _serve_cfg())
    sched.warmup()
    sched.submit(*_workload(60, seed=1))
    # Mid-run: contracts live, table invariants hold (pin agrees with
    # the DEVICE lane — check_table enforces it).
    for _ in range(4):
        sched.step()
    table = np.asarray(sched.carry.table)
    mid_pinned = (table[:, table_lib.FLAGS] & table_lib.PINNED) != 0
    assert mid_pinned.any(), "admission did not stamp any contract"
    check_table(cfg, table)
    sched.run()
    rep = sched.report()
    assert rep.n_sequences == 60 and rep.pinned_accesses > 0
    # Completion released every contract.
    table = np.asarray(sched.carry.table)
    assert ((table[:, table_lib.FLAGS] & table_lib.PINNED) == 0).all()
    check_table(cfg, table)


def test_eviction_under_pressure_spares_pinned_pages():
    # 96 pages total vs ~150 pages of steady demand: the watermark logic
    # must evict cold pages to keep admission alive.
    cfg = _platform(n_fast_pages=32, n_slow_pages=64)
    engine, sched = _run(
        cfg, _serve_cfg(max_live_seqs=40, max_admit_per_step=16,
                        free_low_frac=0.2, free_high_frac=0.3),
        n_seqs=80, seed=2)
    rep = sched.report()
    assert rep.n_sequences == 80
    assert rep.evictions > 0
    # Contracted pages were never victims: every completed sequence
    # released its pin, so none linger in the table...
    table = np.asarray(sched.carry.table)
    assert ((table[:, table_lib.FLAGS] & table_lib.PINNED) == 0).all()


def test_forced_eviction_triggers_refetch():
    cfg = _platform()
    engine = Engine(cfg)
    sched = ContinuousBatchingScheduler(engine, _serve_cfg())
    sched.warmup()
    sched.submit(*_workload(60, seed=4))
    for _ in range(3):
        sched.step()
    # Blow every unpinned page out of the map (a worst-case pressure
    # spike); decode windows now reference evicted pages -> refetch.
    victims = sched.kv.maybe_evict(sched._step_no + 1, extra_needed=1 << 30)
    assert len(victims) and not sched.kv.pinned[victims].any()
    sched.run()
    assert sched.refetches > 0
    assert sched.report().n_sequences == 60


def test_admission_rejects_impossible_prompt():
    cfg = _platform(n_fast_pages=8, n_slow_pages=8)
    engine = Engine(cfg)
    sched = ContinuousBatchingScheduler(
        engine, _serve_cfg(sorted_batch_sizes=(32,), max_pages_per_seq=32))
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        sched.submit([40], [4])
    sched2 = ContinuousBatchingScheduler(
        engine, _serve_cfg(sorted_batch_sizes=(32,), max_pages_per_seq=20))
    sched2.submit([18], [4])
    with pytest.raises(MemoryError, match="never"):
        sched2.run()


# ---------------------------------------------------------------------------
# PagedKVMap
# ---------------------------------------------------------------------------
def test_kv_map_eviction_is_lru_and_skips_pinned():
    cfg = _platform(n_fast_pages=8, n_slow_pages=8)
    kv = PagedKVMap(cfg, max_live_seqs=4, max_pages_per_seq=4,
                    pin_pages_per_seq=1, free_low_frac=0.9,
                    free_high_frac=0.95)
    slots = np.array([0, 0, 1, 1])
    idx = np.array([0, 1, 0, 1])
    pages = kv.alloc(4)
    kv.assign(slots, idx, pages, step=1)
    kv.touch(pages[1:2], 5)               # page idx 1 of slot 0 is hot
    assert kv.pinned[pages[0]] and kv.pinned[pages[2]]
    victims = kv.maybe_evict(step=6, extra_needed=0)
    # Pinned pages (idx 0 of each slot) survive; the cold unpinned page
    # goes first.
    assert pages[3] in victims
    assert not kv.pinned[victims].any()
    assert kv.page_of[1, 1] == -1         # mapping cleared for the victim


def test_kv_map_eviction_breaks_ties_by_page_number():
    """Equally old candidates go lowest page number first, in that order
    (the free-stack order): the victims, and so every emulated serving
    statistic, do not depend on the host CPU's SIMD dispatch."""
    cfg = _platform(n_fast_pages=1024, n_slow_pages=3072)
    kv = PagedKVMap(cfg, max_live_seqs=1024, max_pages_per_seq=4,
                    pin_pages_per_seq=0, free_low_frac=0.5,
                    free_high_frac=0.6)
    pages = kv.alloc(3000)
    kv.assign(np.arange(3000) // 4, np.arange(3000) % 4, pages, step=1)
    kv.touch(pages[::7], 2)               # a younger minority
    victims = kv.maybe_evict(step=3)
    old = np.sort(np.setdiff1d(pages, pages[::7]))
    np.testing.assert_array_equal(victims, old[:len(victims)])
    assert 0 < len(victims) < len(old)


def test_kv_map_release_returns_contracted_pages():
    cfg = _platform(n_fast_pages=8, n_slow_pages=8)
    kv = PagedKVMap(cfg, max_live_seqs=2, max_pages_per_seq=4,
                    pin_pages_per_seq=2)
    pages = kv.alloc(3)
    kv.assign(np.array([0, 0, 0]), np.array([0, 1, 2]), pages, step=1)
    free_before = kv.free_total
    released, contracted = kv.release_slots(np.array([0]))
    assert set(released) == set(pages)
    assert set(contracted) == set(pages[:2])
    assert kv.free_total == free_before + 3


# ---------------------------------------------------------------------------
# contracts
# ---------------------------------------------------------------------------
def test_stamp_pads_to_width_and_rejects_overflow():
    cfg = _platform()
    engine = Engine(cfg)
    state = engine.init_state()
    state = stamp_pin_pages(state, [3, 5], width=8)
    table = np.asarray(state.table)
    stamped = np.flatnonzero(table[:, table_lib.FLAGS]
                             & table_lib.PINNED)
    assert set(stamped) == {3, 5}         # sentinel pad lanes dropped
    check_table(cfg, np.asarray(state.table))
    state = release_pin_pages(state, [3, 5], width=8)
    table = np.asarray(state.table)
    assert ((table[:, table_lib.FLAGS] & table_lib.PINNED) == 0).all()
    with pytest.raises(ValueError, match="exceed the pad width"):
        stamp_pin_pages(state, [1, 2, 3], width=2)


# ---------------------------------------------------------------------------
# satellites: memtier regression + serve_mixed + run_stream prefetch
# ---------------------------------------------------------------------------
def test_tiered_report_zero_pinned_accesses_is_zero_not_nan():
    from repro.memtier.tiered_cache import TieredKVAccounting

    cfg = _platform(chunk=16)
    tier = TieredKVAccounting(cfg, n_layers=1, positions_per_page=16,
                              bytes_per_position=64, pin_pages_per_seq=1)
    # A sequence allocates (and pins) but completes before any decode
    # access lands: zero pinned accesses must read as 0.0, not nan.
    tier._page_for(0, 0)
    tier.free_sequence(0)
    rate = tier.report()["pinned_fast_hit_rate"]
    assert rate == 0.0 and not np.isnan(rate)


def test_serve_mixed_generator_bounds_and_determinism():
    from repro.trace import TraceSpec, generate

    spec = TraceSpec(n_requests=2048, footprint_pages=256, pattern="serve_mixed",
                     n_tenants=4, prefill_frac=0.3, decode_window=4, seed=7)
    t1, t2 = generate(spec), generate(spec)
    pages = np.asarray(t1.page)
    assert np.array_equal(pages, np.asarray(t2.page))   # deterministic
    assert pages.min() >= 0 and pages.max() < 256       # in-footprint
    assert 0 < np.asarray(t1.is_write).mean() < 1       # mixed traffic


def test_run_stream_prefetch_is_bitwise_neutral():
    from repro.trace import TraceSpec, generate

    cfg = _platform()
    segs = [generate(TraceSpec(n_requests=n, footprint_pages=256, seed=s))
            for s, n in enumerate((40, 96, 23))]
    base = Engine(cfg).run_stream(iter(segs))
    pre = Engine(cfg).run_stream(iter(segs), prefetch=2)
    for k in base.outs:
        assert np.array_equal(np.asarray(base.outs[k]),
                              np.asarray(pre.outs[k]))
    for a, b in zip(jax.tree.leaves(base.state), jax.tree.leaves(pre.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
