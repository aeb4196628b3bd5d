"""Self time and scope attribution of the trace reduction by named scope
(hbench/scopes.py), on intervals worked out by hand."""
import gzip
import struct

import numpy as np
import pytest

from hbench import profile, scopes

BODY = "jit(_emulate_impl)/while/body/closed_call"


def test_scope_of_names_phase_and_stage():
    assert scopes.scope_of(f"{BODY}/hmmu.commit/decay/cond") == \
        ("hmmu.commit", "decay")
    assert scopes.scope_of(f"{BODY}/hmmu.pipeline/lookup/jit(clip)/max") \
        == ("hmmu.pipeline", "lookup")
    # A phase with no stage scope, and a name that is not a stage.
    assert scopes.scope_of(f"{BODY}/hmmu.policy/jit(remainder)/eq") == \
        ("hmmu.policy", "")
    assert scopes.scope_of(f"{BODY}/hmmu.pipeline/gather") == \
        ("hmmu.pipeline", "")
    # The innermost hmmu scope wins.
    assert scopes.scope_of("hmmu.pipeline/x/hmmu.commit/scatter/add") == \
        ("hmmu.commit", "scatter")
    assert scopes.scope_of("jit(_emulate_impl)/while") == (scopes.OTHER, "")
    assert scopes.scope_of("") == (scopes.OTHER, "")


def test_self_times_by_hand():
    # while [0, 10) holds cond [1, 5) holding fusion [2, 4); a copy
    # [6, 8) in the while's body; a later op [12, 13) alone.
    s = np.array([0.0, 1.0, 2.0, 6.0, 12.0])
    e = np.array([10.0, 5.0, 4.0, 8.0, 13.0])
    own = scopes.self_times(s, e)
    assert own.tolist() == [10 - 4 - 2, 4 - 2, 2.0, 2.0, 1.0]
    assert own.sum() == profile.union_length(s, e)


def test_self_times_overlap_without_nesting_adds_to_union():
    # [0, 4) and [2, 6) overlap without nesting: [2, 6) started last and
    # takes [2, 6); two identical intervals give one of them the time.
    own = scopes.self_times(np.array([0.0, 2.0, 7.0, 7.0]),
                            np.array([4.0, 6.0, 9.0, 9.0]))
    assert own.tolist()[:2] == [2.0, 4.0]
    assert sorted(own.tolist()[2:]) == [0.0, 2.0]
    assert own.sum() == 8.0


def _named(ops):
    """Profile-style ops named by event, and the event -> op_name map."""
    events, names = {}, {}
    for dev, (ops_, st, en) in ops.items():
        evs = [f"%op.{dev}.{i}" for i in range(len(ops_))]
        events[dev] = (evs, st, en)
        names[dev] = {e: o for e, o in zip(evs, ops_) if o}
    return events, names


def test_reduce_by_hand():
    # Window [10, 30). Device 0: a while [8, 28) clipped to [10, 28)
    # holding a cond [12, 20) under hmmu.commit/decay, which holds a
    # fusion [13, 17) under hmmu.commit/scatter; an unscoped copy
    # [21, 24) in the while; a pipeline op [27, 32) clipped to [27, 30)
    # overlapping the while's end; an op [31, 33) outside the window.
    names = ["jit(f)/while", f"{BODY}/hmmu.commit/decay/cond",
             f"{BODY}/hmmu.commit/scatter/scatter-add", "",
             f"{BODY}/hmmu.pipeline/rx/add", f"{BODY}/hmmu.policy/eq"]
    st = np.array([8.0, 12.0, 13.0, 21.0, 27.0, 31.0])
    en = np.array([28.0, 20.0, 17.0, 24.0, 32.0, 33.0])
    # Device 1: one counters op [10, 14) and one pipeline op [15, 16).
    ops = {0: (names, st, en),
           1: ([f"{BODY}/hmmu.counters/add", f"{BODY}/hmmu.pipeline/tx/x"],
               np.array([10.0, 15.0]), np.array([14.0, 16.0]))}
    t = scopes.reduce(*_named(ops), 10.0, 30.0)
    # Device 0: while 18 - 8 - 3 - 1 = 6 (its last second goes to the
    # pipeline op that started inside it), cond 8 - 4 = 4, fusion 4,
    # copy 3, pipeline 3; busy 20. Device 1: counters 4, pipeline 1.
    assert t.busy_s == (20.0 + 5.0) / 2
    assert t.seconds == {
        (scopes.OTHER, ""): (6.0 + 3.0) / 2,
        ("hmmu.commit", "decay"): 4.0 / 2,
        ("hmmu.commit", "scatter"): 4.0 / 2,
        ("hmmu.pipeline", "rx"): 3.0 / 2,
        ("hmmu.pipeline", "tx"): 1.0 / 2,
        ("hmmu.counters", ""): 4.0 / 2,
    }
    assert sum(t.seconds.values()) == t.busy_s
    assert t.scoped
    assert t.phase_s("hmmu.commit") == 4.0
    busy = [profile.union_length(np.clip(s, 10, 30), np.clip(e, 10, 30))
            for _, s, e in ops.values()]
    assert t.busy_s == sum(busy) / 2

    # Eight chunk steps: the groups add up to busy time per step.
    g = t.groups_us(8)
    assert g == {"pipeline": 1e6 * 2.0 / 8, "commit": 1e6 * 4.0 / 8,
                 "policy": 0.0, "other": 1e6 * 6.5 / 8}
    assert sum(g.values()) == 1e6 * t.busy_s / 8
    rows = t.table(8)
    assert [r.split()[1:3] for r in rows[:3]] == [
        ["hmmu.pipeline", "rx"], ["hmmu.pipeline", "lookup"],
        ["hmmu.pipeline", "banks"]]
    # Every listed scope gets a line, with 0 where nothing ran under it.
    n_scopes = sum(len(scopes.STAGES.get(p, ("",))) for p in scopes.PHASES)
    assert len(rows) == n_scopes + 1
    assert rows[-1].split()[1] == scopes.OTHER
    assert rows[1].split()[3] == "0.000"


def test_program_without_scopes_reads_nothing():
    ops = {0: (["jit(f)/while", "jit(f)/while/body/add"],
               np.array([0.0, 1.0]), np.array([4.0, 2.0]))}
    t = scopes.reduce(*_named(ops), 0.0, 4.0)
    assert t.seconds == {(scopes.OTHER, ""): 4.0}
    assert not t.scoped
    assert [r.split()[1] for r in t.table(1)] == [scopes.OTHER]


def _varint(n):
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, value):
    """One protobuf field: a varint for an int, a length-delimited field
    for bytes or str, a fixed64 for a float."""
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace(with_op_names=True):
    """A serialized XSpace of one device plane and one host plane, by
    the field numbers of tsl's xplane.proto."""
    tf_op, flops, ref = 7, 3, 9
    stat_md = [(tf_op, "tf_op"), (flops, "flops"),
               (ref, "jit(f)/while:")]
    events = [
        (1, "%reshape.1 = s32[8] reshape(s32[2,4] %p)",
         _f(5, _f(1, tf_op) +
            _f(5, f"{BODY}/hmmu.commit/scatter/reshape:")) +
         _f(5, _f(1, flops) + _f(2, 1.5))),
        (2, "%copy.2 = s32[2,4] copy(s32[2,4] %p)",
         _f(5, _f(1, tf_op) + _f(7, ref))),
        (3, "%copy-start.3 = s32[8] copy-start(s32[8] %q)",
         _f(5, _f(1, flops) + _f(2, 0.0))),
    ]
    if not with_op_names:
        events = [(i, n, b"") for i, n, _ in events]
    device = _f(1, 4) + _f(2, "/device:TPU:0") + \
        _f(3, _f(2, "XLA Ops") + _f(4, _f(1, 1) + _f(2, 5) + _f(3, 7)))
    for i, name, stats in events:
        device += _f(4, _f(1, i) + _f(2, _f(1, i) + _f(2, name) +
                                      _f(4, name.split(" ")[0]) + stats))
    for i, name in stat_md:
        device += _f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, name)))
    host = _f(2, "/host:CPU") + _f(4, _f(1, 1) + _f(2, _f(2, "%x = y")))
    return _f(1, device) + _f(1, host) + _f(4, "host-name")


def test_op_names_walks_the_event_metadata():
    assert scopes.op_names(_xspace()) == {0: {
        "%reshape.1 = s32[8] reshape(s32[2,4] %p)":
            f"{BODY}/hmmu.commit/scatter/reshape",
        "%copy.2 = s32[2,4] copy(s32[2,4] %p)": "jit(f)/while"}}


def test_a_trace_without_op_names_is_an_error(tmp_path):
    path = tmp_path / "t.xplane.pb.gz"
    path.write_bytes(gzip.compress(_xspace(with_op_names=False)))
    with pytest.raises(scopes.NoOpNames, match="op_name"):
        scopes.load_op_names(path)
    path.write_bytes(gzip.compress(_xspace()))
    assert len(scopes.load_op_names(path)[0]) == 2
