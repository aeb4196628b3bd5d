"""A run whose timed path is broken underneath comes out not correct.

Each case runs a tiny cell through the whole harness on the CPU, the look
for a chip skipped, with one fault planted in the program's entry point:
a step that returns its state unchanged, half of the batch left out, or
an answer altered where it is produced. A sound run of the same cell
comes out correct."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from hbench import bench
from hbench.tests import tiny


def _unchanged_run(orig):
    def run(self, trace, *, state=None, **kw):
        if state is None:
            return orig(self, trace, state=state, **kw)
        r = orig(self, trace, state=state, donate=False, **kw)
        return r._replace(state=state)
    return run


def _half_batch_run(orig):
    def run(self, trace, *, valid=None, **kw):
        n = len(trace)
        half = jnp.arange(n) < n // 2
        valid = half if valid is None else valid & half
        return orig(self, trace, valid=valid, **kw)
    return run


def _altered_run(orig):
    def run(self, trace, **kw):
        r = orig(self, trace, **kw)
        outs = dict(r.outs)
        outs["latency"] = outs["latency"].at[0].add(1)
        return r._replace(outs=outs)
    return run


def _unchanged_sweep(orig):
    def cont(self, result, trace, **kw):
        r = orig(self, result, trace, donate=False, **kw)
        return dataclasses.replace(r, states=result.states)
    return cont


def _half_batch_sweep(orig):
    def cont(self, result, trace, **kw):
        r = orig(self, result, trace, **kw)
        p = len(r.points)

        def half(x):
            return jnp.concatenate([x[:p // 2], x[:p - p // 2]])
        return dataclasses.replace(r, outs=jax.tree.map(half, r.outs),
                                   states=jax.tree.map(half, r.states))
    return cont


def _altered_sweep(orig):
    def cont(self, result, trace, **kw):
        r = orig(self, result, trace, **kw)
        outs = dict(r.outs)
        outs["returns"] = outs["returns"].at[0, 0].add(1)
        return dataclasses.replace(r, outs=outs)
    return cont


FAULTS = {
    "tiny.stream": ("run", {"unchanged": _unchanged_run,
                            "half_batch": _half_batch_run,
                            "altered": _altered_run}),
    "tiny.grid": ("continue_sweep", {"unchanged": _unchanged_sweep,
                                     "half_batch": _half_batch_sweep,
                                     "altered": _altered_sweep}),
    "tiny.serve": ("run", {"unchanged": _unchanged_run,
                           "half_batch": _half_batch_run,
                           "altered": _altered_run}),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("root"))


def _run(root, cell):
    return bench.run_cell(root, cell, 2 ** 31 + 99, 1.0, False,
                          time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_sound_run_is_correct(root, cell):
    r = _run(root, cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(FAULTS)
                                        for f in FAULTS[c][1]])
def test_fault_is_not_correct(root, cell, fault, monkeypatch):
    from repro import Engine

    method, faults = FAULTS[cell]
    monkeypatch.setattr(Engine, method,
                        faults[fault](getattr(Engine, method)))
    r = _run(root, cell)
    assert not r["correct"], r["checks"]
