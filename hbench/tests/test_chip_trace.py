"""The trace reductions on a trace recorded on a TPU v5e: the tiny stream
cell through the whole harness, with the program's named scopes in.

``data/tiny_stream.xplane.pb.gz`` is the profiler trace and
``data/tiny_stream.json`` the run's result line, as
``hbench/trace_scopes.py`` wrote them (its docstring gives the command).
"""
import json
import pathlib

import numpy as np
import pytest

from hbench import profile, scopes

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "tiny_stream.xplane.pb.gz"
CALL = "jit__emulate_impl("   # the program each engine.run call launches


@pytest.fixture(scope="module")
def trace():
    data = profile.load(TRACE)
    ops, spans = profile.read_events(data)
    modules = sorted(
        (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
        for plane in data.planes if plane.name == "/device:TPU:0"
        for line in plane.lines if line.name == "XLA Modules"
        for ev in line.events if ev.name.startswith(CALL))
    result = json.loads((DATA / "tiny_stream.json").read_text())
    return ops, spans, modules, result


def test_device_ops_fall_inside_the_window(trace):
    ops, spans, _, result = trace
    assert list(ops) == [0]
    r = profile.reduce(ops, spans)
    (lo, hi), = [(a, b) for n, a, b in spans if n == profile.WINDOW]
    _, st, en = ops[0]
    inside = (en > lo) & (st < hi)
    assert inside.sum() > 1000
    assert 0 < r.busy_s[0] <= r.window_s
    # The recorded run read its metrics from this same trace.
    steps = result["notes"]["chunks"] * result["notes"]["points"]
    per_chunk = 1e6 * r.busy_s[0] / steps
    assert result["metrics"]["device_us_per_chunk.run"]["value"] == \
        pytest.approx(per_chunk, rel=1e-12)
    assert result["scopes"]["busy_us_per_chunk"] == \
        pytest.approx(per_chunk, rel=1e-12)


def test_each_call_runs_when_its_span_opens(trace):
    # Host spans and device operations share one clock: every
    # Engine.run call of the window launches one program, which the
    # device runs once the call's span has opened on the host. The
    # profiler maps device times onto the host clock with an error of a
    # fraction of a millisecond: on this trace the first call's program
    # starts 0.27 ms before its span opens. So each program starts within
    # 0.5 ms before and 5 ms after its span opens (a call takes about 2
    # ms on the device), and its operations lie inside it.
    ops, spans, modules, _ = trace
    (lo, hi), = [(a, b) for n, a, b in spans if n == profile.WINDOW]
    calls = sorted(a for n, a, b in spans
                   if n == "engine.run" and lo <= a < hi)
    assert len(calls) >= 2
    assert len(modules) == len(calls)
    _, st, en = ops[0]
    for opened, (m_lo, m_hi) in zip(calls, modules):
        assert -0.5e-3 <= m_lo - opened <= 5e-3
        mine = (st >= m_lo) & (st < m_hi)
        assert mine.any() and en[mine].max() <= m_hi


def test_scopes_attribute_every_phase_and_add_up(trace):
    ops, spans, _, _ = trace
    r = profile.reduce(ops, spans)
    (lo, hi), = [(a, b) for n, a, b in spans if n == profile.WINDOW]
    t = scopes.reduce(ops, scopes.load_op_names(TRACE), lo, hi)
    for phase in scopes.PHASES:
        assert t.phase_s(phase) > 0, phase
    assert sum(t.seconds.values()) == pytest.approx(r.busy_s[0], rel=1e-3)
    assert t.busy_s == pytest.approx(r.busy_s[0], rel=1e-12)
    # Most of the time is under a named scope; the rest is the loop.
    assert t.phase_s(scopes.OTHER) < 0.9 * t.busy_s
    assert np.isclose(sum(t.groups_us(1).values()), 1e6 * t.busy_s)
