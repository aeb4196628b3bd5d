"""The redirection table's two forms give the same chunk step, bit for bit.

``EmulatorState.table`` is the packed ``int32[n_pages, 8]`` table; from
``emulator.FLAT_CARRY_MIN_BYTES`` of table up, the emulator's scan carries
the flat lane-major ``int32[8 * n_pages]`` form (``core.table``).
``step_ref`` on a packed table and on its flat form must give the same
table, the same per-request outputs and the same scalars, under every
registered policy, with and without a retirement firing and with swaps
completing; whole runs and sweeps must give the same results with either
carry. The flat form's sentinel-row writes must drop, not land on row 0
of the next lane.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_trace_arrays
from repro import Engine
from repro.core import FaultPlan, Trace, init_state, small_platform
from repro.core import emulator
from repro.core import table as table_lib
from repro.core.emulator import as_registry
from repro.core.policies import POLICIES
from repro.kernels import chunk_step as cs
from repro.sweep import SweepSpec

N_CHUNKS = 24


def _scalars(state):
    return cs.StepScalars(
        clock=state.clock, clock_ptr=state.clock_ptr,
        chunk_idx=state.chunk_idx, dma=state.dma,
        link_free_rx=state.link_free_rx, link_free_tx=state.link_free_tx,
        last_return=state.last_return, rescue_page=state.rescue_page,
        min_wear=state.min_wear, fault_cursor=state.fault_cursor)


@functools.lru_cache(maxsize=None)
def _step(cfg, registry):
    return jax.jit(functools.partial(cs.step_ref, cfg, registry))


def _assert_same(got, want, what):
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{what} leaf {i}")


@pytest.mark.parametrize("death", [False, True], ids=["no_fault", "death"])
@pytest.mark.parametrize("policy", list(POLICIES))
def test_step_ref_is_the_same_on_both_forms(policy, death):
    """Chunk by chunk from one state: the flat form's step, unflattened,
    equals the packed form's step in every output."""
    cfg = small_platform(hot_threshold=2, decay_every=4, write_weight=3,
                         policy=policy)
    registry = as_registry(None)
    params = cfg.runtime()
    rng = np.random.default_rng(7)
    page, offset, is_write, size = (
        jnp.asarray(a) for a in make_trace_arrays(
            cfg, N_CHUNKS * cfg.chunk, rng, hot_fraction=0.6))
    # A fast-tier page the trace touches dies at chunk 3: the retirement
    # stamps it and its rescue swap commits a few chunks later.
    victim = int(np.asarray(page)[np.asarray(page) < cfg.n_fast_pages][0])
    faults = (FaultPlan.of(deaths=[(3, victim)]) if death
              else FaultPlan.empty())
    state = init_state(cfg, params)
    table, sc, bank_free = state.table, _scalars(state), state.bank_free
    step = _step(cfg, registry)
    valid = jnp.ones(cfg.chunk, bool)
    retired, swaps0 = [], int(sc.dma.swaps_done)
    for k in range(N_CHUNKS):
        sl = slice(k * cfg.chunk, (k + 1) * cfg.chunk)
        args = (params, sc, bank_free, page[sl], offset[sl], is_write[sl],
                size[sl], valid, faults)
        want = step(table, *args)
        got = step(table_lib.to_flat(table), *args)
        assert got[0].shape == (table_lib.ROW_W * cfg.n_pages,)
        np.testing.assert_array_equal(
            np.asarray(table_lib.to_packed(got[0])), np.asarray(want[0]),
            err_msg=f"table after chunk {k}")
        _assert_same(got[1:], want[1:], f"chunk {k}")
        table, sc, bank_free, outs = want
        retired.append(int(outs["retired"]))
    table_lib.check_table(cfg, np.asarray(table))
    if death:
        assert victim in retired
    if death or policy != "static":
        assert int(sc.dma.swaps_done) > swaps0, "no swap completed"


def _random_table(n_pages, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**20, (n_pages, table_lib.ROW_W)),
                       jnp.int32)


@pytest.mark.parametrize("write", ["add_at", "set_at"])
def test_sentinel_row_write_drops_on_the_flat_form(write):
    """Row ``n_pages`` is the commit's "no write" sentinel. On the flat
    form ``lane * n_pages + n_pages`` is row 0 of the next lane, so the
    accessor must drop it explicitly: no lane of any row changes."""
    n = 12
    packed = _random_table(n)
    flat = table_lib.to_flat(packed)
    fn = getattr(table_lib, write)
    lanes = jnp.arange(table_lib.ROW_W - 1, dtype=jnp.int32)
    rows = jnp.full(lanes.shape, n, jnp.int32)
    upd = jnp.full(lanes.shape, 99, jnp.int32)
    np.testing.assert_array_equal(np.asarray(fn(flat, rows, lanes, upd)),
                                  np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(fn(packed, rows, lanes, upd)),
                                  np.asarray(packed))
    # The same write one row lower lands on each lane's last row.
    got = table_lib.to_packed(fn(flat, rows - 1, lanes, upd))
    want = fn(packed, rows - 1, lanes, upd)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(want), np.asarray(packed))


def test_accessors_agree_on_both_forms():
    """Every table-level accessor reads and writes the same values on
    both forms, for indices inside, past and before the table (jnp's
    rules: gathers count negatives from the end and clamp; writes drop
    what lies outside)."""
    n = 10
    packed = _random_table(n, seed=5)
    flat = table_lib.to_flat(packed)
    idx = jnp.asarray([0, 3, n - 1, n, n + 7, -1, -n, -n - 4], jnp.int32)
    lanes = jnp.asarray([0, 1, 2, 3, 4, 5, 6, 7], jnp.int32)
    upd = jnp.arange(1, 9, dtype=jnp.int32)

    np.testing.assert_array_equal(np.asarray(table_lib.rows(flat, idx)),
                                  np.asarray(table_lib.rows(packed, idx)))
    for lane in range(table_lib.ROW_W):
        np.testing.assert_array_equal(
            np.asarray(table_lib.lane_at(flat, idx, lane)),
            np.asarray(table_lib.lane_at(packed, idx, lane)))
        np.testing.assert_array_equal(
            np.asarray(table_lib.column(flat, lane)),
            np.asarray(table_lib.column(packed, lane)))
    np.testing.assert_array_equal(
        np.asarray(table_lib.lane_at(flat, idx, lanes)),
        np.asarray(table_lib.lane_at(packed, idx, lanes)))
    for fn in (table_lib.add_at, table_lib.set_at):
        np.testing.assert_array_equal(
            np.asarray(table_lib.to_packed(fn(flat, idx, lanes, upd))),
            np.asarray(fn(packed, idx, lanes, upd)))
    col = jnp.arange(n, dtype=jnp.int32) * 3
    np.testing.assert_array_equal(
        np.asarray(table_lib.to_packed(
            table_lib.set_column(flat, table_lib.HOTNESS, col))),
        np.asarray(table_lib.set_column(packed, table_lib.HOTNESS, col)))
    np.testing.assert_array_equal(
        np.asarray(table_lib.to_packed(table_lib.decay_hotness(flat, 2))),
        np.asarray(table_lib.decay_hotness(packed, 2)))
    assert table_lib.n_pages(flat) == table_lib.n_pages(packed) == n
    np.testing.assert_array_equal(
        np.asarray(table_lib.to_packed(flat)), np.asarray(packed))


@pytest.fixture
def carry(monkeypatch):
    """Force the scan's carried form: ``carry("flat")`` or
    ``carry("packed")``. The compiled entry points do not key on the
    form, so every cache is emptied around each choice."""

    def force(form):
        emulator._ENTRY_CACHE.clear()
        jax.clear_caches()
        monkeypatch.setattr(emulator, "FLAT_CARRY_MIN_BYTES",
                            0 if form == "flat" else 2**62)

    yield force
    emulator._ENTRY_CACHE.clear()
    jax.clear_caches()


def _results(case):
    base = small_platform(chunk=8, hot_threshold=2, decay_every=4,
                          policy="wear_level")
    rng = np.random.default_rng(13)
    t = Trace(*(jnp.asarray(x) for x in
                make_trace_arrays(base, 160, rng, hot_fraction=0.5)))
    engine = Engine(base)
    if case == "sweep":
        spec = SweepSpec(base=base, technologies=("3dxpoint", "stt-ram"),
                         fast_fractions=(0.125,),
                         policies=("hotness", "wear_level"),
                         link_lats=(40,))
        first = engine.sweep(spec, t)
        cont = engine.continue_sweep(first, t, donate=False)
        return [first.states, first.outs, cont.states, cont.outs]
    faults = (FaultPlan.of(deaths=[(2, int(t.page[3])), (6, 60)])
              if case == "death" else None)
    first = engine.run(t, donate=False, faults=faults)
    cont = engine.run(t, state=first.state, donate=True, faults=faults)
    return [first.outs, cont.state, cont.outs]


@pytest.mark.parametrize("case", ["run", "death", "sweep"])
def test_engine_results_do_not_depend_on_the_carried_form(carry, case):
    """A run with a continuation (donated), one with frame deaths, and a
    sweep with its continuation give the same outputs and final states
    whether the scan carries the flat or the packed table."""
    carry("packed")
    assert not emulator.carries_flat_table(small_platform(chunk=8))
    want = _results(case)
    carry("flat")
    assert emulator.carries_flat_table(small_platform(chunk=8))
    got = _results(case)
    _assert_same(got, want, case)
