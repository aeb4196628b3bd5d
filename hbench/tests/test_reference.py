"""The reference's closed forms against the literal per-request loops, and
the reference against the platform at a tiny size."""
import dataclasses

import numpy as np
import pytest

from hbench import compare
from hbench import reference as ref


def _loop_maxplus(arrival, service):
    done, prev = [], None
    for a, s in zip(arrival, service):
        prev = (a if prev is None else max(a, prev)) + s
        done.append(prev)
    return np.array(done)


def _loop_banks(arrival, service, bank, free):
    free = free.copy()
    done = []
    for a, s, b in zip(arrival, service, bank):
        free[b] = max(a, free[b]) + s
        done.append(free[b])
    return np.array(done), free


@pytest.mark.parametrize("seed", range(5))
def test_closed_forms_equal_the_loops(seed):
    rng = np.random.default_rng(seed)
    n = 64
    arrival = np.sort(rng.integers(0, 500, n)) + rng.integers(0, 50, n)
    service = rng.integers(0, 40, n)
    bank = rng.integers(0, 6, n)
    free = rng.integers(0, 700, 6)
    np.testing.assert_array_equal(ref.maxplus(arrival, service),
                                  _loop_maxplus(arrival, service))
    got, got_free = ref.bank_queues(arrival, service, bank, free)
    want, want_free = _loop_banks(arrival, service, bank, free)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_free, want_free)


def _techs():
    from repro.core.config import TECHNOLOGIES

    return {k: dict(read_lat=v.read_lat, write_lat=v.write_lat,
                    bytes_per_cycle=v.bytes_per_cycle)
            for k, v in TECHNOLOGIES.items()}


def _platform(cfg):
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["fast"], d["slow"] = cfg.fast.name, cfg.slow.name
    return d


@pytest.mark.parametrize("policy", ["hotness", "write_bias", "static"])
def test_reference_equals_the_platform(policy):
    import jax

    from repro import Engine
    from repro.core import paper_platform
    from repro.trace import TraceSpec, generate

    cfg = paper_platform().with_(n_fast_pages=64, n_slow_pages=448,
                                 chunk=16, hot_threshold=3, decay_every=8,
                                 write_weight=4, policy=policy)
    engine = Engine(cfg)
    pf = ref.Platform(_platform(cfg), _techs())
    st, ctl = ref.State(pf), ref.State(pf)
    program, control = compare.Tally(), compare.Tally()
    state = None
    for seg in range(3):
        t = generate(TraceSpec(n_requests=1024, footprint_pages=400,
                               write_frac=0.4, zipf_alpha=0.9, seed=seg))
        r = engine.run(t, state=state)
        state = r.state
        host = [np.asarray(x) for x in t]
        want = ref.run(pf, st, *host)
        program.outs({k: np.asarray(r.outs[k]) for k in compare.OUT_KEYS},
                     want, f"segment {seg}")
        control.outs(ref.run(pf, ctl, *host, redirect=False), want,
                     f"segment {seg}")
    want = compare.reference_state(st)
    program.state(compare.program_state(jax.device_get(state)), want, "end")
    control.state(compare.reference_state(ctl), want, "end")
    assert program.correct(), program.where
    if policy != "static":   # the static policy never migrates
        assert st.s["swaps_done"] > 0
        assert not control.correct()


def test_pin_contracts_equal_the_platform():
    import jax

    from repro import Engine
    from repro.core import paper_platform
    from repro.serve import release_pin_pages, stamp_pin_pages
    from repro.trace import TraceSpec, generate

    cfg = paper_platform().with_(n_fast_pages=64, n_slow_pages=448,
                                 chunk=16, hot_threshold=2, decay_every=8)
    engine = Engine(cfg)
    pf = ref.Platform(_platform(cfg), _techs())
    st = ref.State(pf)
    tally = compare.Tally()
    rng = np.random.default_rng(0)
    state = engine.init_state()
    for seg in range(6):
        pins = rng.choice(512, size=12, replace=False)
        state = stamp_pin_pages(state, pins, width=16)
        ref.stamp_pins(pf, st, pins)
        t = generate(TraceSpec(n_requests=512, footprint_pages=300,
                               write_frac=0.4, zipf_alpha=0.9, seed=seg))
        r = engine.run(t, state=state)
        state = r.state
        want = ref.run(pf, st, *[np.asarray(x) for x in t])
        tally.outs({k: np.asarray(r.outs[k]) for k in compare.OUT_KEYS},
                   want, f"segment {seg}")
        free = pins[:6]
        state = release_pin_pages(state, free, width=16)
        ref.release_pins(pf, st, free)
    tally.state(compare.program_state(jax.device_get(state)),
                compare.reference_state(st), "end")
    assert tally.correct(), tally.where
    assert np.any(st.lanes["flags"] != 0) and st.s["swaps_done"] > 0
