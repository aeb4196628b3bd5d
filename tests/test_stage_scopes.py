"""The chunk step's named scopes reach XLA's ``op_name`` metadata, and they
change nothing the emulator computes.

A device trace attributes each operation's time to the ``hmmu.*`` phase
and stage in its ``op_name`` (``hbench/scopes.py``); these tests check on
the CPU that every scope survives lowering and compilation of the scan
program, for one run and for a vmapped sweep, and that a run with the
scopes is bitwise the run without them.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_trace_arrays
from repro import Engine
from repro.core import Trace, emulator, pad_trace, small_platform
from repro.sweep import SweepSpec

PHASES = ("hmmu.pipeline", "hmmu.commit", "hmmu.retire", "hmmu.policy",
          "hmmu.counters")
STAGE_SCOPES = {
    "hmmu.pipeline": ("rx", "lookup", "banks", "return", "tx"),
    "hmmu.commit": ("deltas", "scatter", "decay", "scrub")}
SCOPES = [(p, None) for p in PHASES] + \
    [(p, s) for p, ss in STAGE_SCOPES.items() for s in ss]


def _setup(n=256):
    cfg = small_platform(n_fast_pages=16, n_slow_pages=112, decay_every=4)
    arrays = make_trace_arrays(cfg, n, np.random.default_rng(3))
    padded, valid = pad_trace(cfg, Trace(*(jnp.asarray(a) for a in arrays)))
    return cfg, Engine(cfg), padded, valid


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.fixture(scope="module")
def programs():
    """Compiled HLO text of the single-run program and of a 2-point
    vmapped sweep."""
    cfg, engine, padded, valid = _setup()
    run = jax.jit(emulator._emulate_impl, static_argnames=("cfg", "registry"))
    run_text = run.lower(engine._static, engine.registry, padded, valid,
                         None, engine.params).compile().as_text()
    spec = SweepSpec(base=cfg, policies=("hotness", "static"))
    _, registry, params = engine._sweep_batch(spec)
    sweep = jax.jit(emulator._emulate_batch_impl,
                    static_argnames=("cfg", "registry"))
    sweep_text = sweep.lower(engine._static, registry, padded, valid, None,
                             params).compile().as_text()
    return {"run": _op_names(run_text), "sweep": _op_names(sweep_text)}


@pytest.mark.parametrize("program", ["run", "sweep"])
@pytest.mark.parametrize("phase,stage", SCOPES,
                         ids=[f"{p}/{s}" if s else p for p, s in SCOPES])
def test_scope_in_compiled_op_names(programs, program, phase, stage):
    want = f"/{phase}/" if stage is None else f"/{phase}/{stage}/"
    names = programs[program]
    assert any(want in n for n in names), (
        f"no op_name of the compiled {program} program holds {want!r}")


@contextlib.contextmanager
def _no_named_scopes():
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = real


def _run_fresh(engine, padded, valid):
    # A new function object per call, so neither run reuses the other's
    # trace or executable.
    fn = jax.jit(lambda t, v, p: emulator._emulate_impl(
        engine._static, engine.registry, t, v, None, p))
    lowered = fn.lower(padded, valid, engine.params)
    state, outs = lowered.compile()(padded, valid, engine.params)
    return jax.device_get((state, outs)), lowered.as_text(
        debug_info=True)


def test_scopes_change_no_result():
    _, engine, padded, valid = _setup(n=512)
    with_scopes, text = _run_fresh(engine, padded, valid)
    with _no_named_scopes():
        without, text_bare = _run_fresh(engine, padded, valid)
    assert "hmmu.commit" in text and "hmmu.commit" not in text_bare
    a, b = jax.tree.leaves(with_scopes), jax.tree.leaves(without)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
