"""Device time of the chunk step by phase and stage, read from the named
scopes the program puts on it.

The program runs each phase of a chunk step under ``jax.named_scope``
(``kernels/chunk_step.py`` ``step_ref``, ``core/emulator.py``
``_chunk_step``), and most stages under a scope nested in their phase
(``PHASES``, ``STAGES``). XLA keeps the scopes in each operation's
``op_name`` metadata, and on a TPU the profiler writes it into the
metadata of each operation's events on the device plane: the stat
``tf_op`` (``OP_NAME_STAT``) of the event's ``XEventMetadata``, as
``op_name:op_type``, for example
``jit(_emulate_impl)/while/body/closed_call/hmmu.policy/select_n:``.
Operations XLA inserts without metadata, such as asynchronous copies,
have no ``tf_op``.

Per device, over the events of its ``XLA Ops`` line:

* each operation's interval is clipped to the ``window`` span;
* its self time is the clipped duration less the union of the operations
  nested inside it on the same line (the scan's ``while`` holds its
  body's operations, a ``cond`` its branch's); where intervals overlap
  without nesting, each instant goes to the operation that started last,
  so the self times of a line add up to its busy time;
* the self time goes to the innermost ``hmmu.*`` phase in the operation's
  ``op_name`` and to the stage named just inside that phase; an
  operation with no ``hmmu.*`` scope (the scan's loop and carry, a copy
  XLA inserted) counts as ``other``.

A fusion carries the ``op_name`` of the operation it was built around,
so its whole time goes to that operation's scope.
"""
from __future__ import annotations

import gzip
import math
import pathlib
from dataclasses import dataclass

import numpy as np

from hbench import profile

OP_NAME_STAT = "tf_op"
PHASES = ("hmmu.pipeline", "hmmu.commit", "hmmu.retire", "hmmu.policy",
          "hmmu.counters")
STAGES = {"hmmu.pipeline": ("rx", "lookup", "banks", "return", "tx"),
          "hmmu.commit": ("deltas", "scatter", "decay", "scrub")}
OTHER = "other"
# What a per-chunk metric of each part of the chunk step would read: the
# scan's loop, carry and unscoped copies go with the counter fold, so
# the four add up to the busy time.
GROUPS = {"pipeline": ("hmmu.pipeline",), "commit": ("hmmu.commit",),
          "policy": ("hmmu.retire", "hmmu.policy"),
          "other": (OTHER, "hmmu.counters")}


class NoOpNames(ValueError):
    """The trace names no operation's ``op_name``: nothing to attribute."""


def scope_of(op_name: str) -> tuple[str, str]:
    """(phase, stage) of an ``op_name``: the innermost ``hmmu.*`` scope and
    the stage scope just inside it ('' where there is none), or
    (``OTHER``, '')."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i].startswith("hmmu."):
            inner = parts[i + 1] if i + 1 < len(parts) else ""
            return parts[i], inner if inner in STAGES.get(parts[i], ()) \
                else ""
    return OTHER, ""


def self_times(starts, ends) -> np.ndarray:
    """Self time of each interval ``[starts[i], ends[i])``: the instants
    in it that no interval started later (or as late and shorter) also
    covers. For nested intervals, an interval's length less the union of
    those nested inside it. The self times add up to the union's
    length."""
    st, en = np.asarray(starts, float), np.asarray(ends, float)
    order = np.lexsort((-en, st)).tolist()
    st, en = st.tolist(), en.tolist()
    out = [0.0] * len(st)
    stack: list[int] = []   # open intervals, the one started last on top
    t = -math.inf           # instants before t are given out
    for i in order + [None]:
        s = math.inf if i is None else st[i]
        # Give [t, s) to the interval on top of the stack, closing those
        # that end on the way.
        while stack:
            top = stack[-1]
            e = en[top]
            if e <= t:
                stack.pop()
            elif e > s:
                out[top] += s - t
                break
            else:
                out[top] += e - t
                t = e
                stack.pop()
        t = max(t, s)
        if i is not None:
            stack.append(i)
    return np.asarray(out)


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: an
    int for a varint, a ``(start, end)`` span for a length-delimited
    field. A field's bytes are skipped, not read, until asked for."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} in an XSpace")
        yield key >> 3, v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode()


def op_names(raw: bytes) -> dict[int, dict[str, str]]:
    """``{device id: {event name: op_name}}`` from the event metadata of
    each device plane of a serialized ``XSpace``: the stat
    ``OP_NAME_STAT`` of each operation, held as ``op_name:op_type``.
    ``jax.profiler.ProfileData`` gives an event's stats but not its
    metadata's, so this walks the protobuf (tsl ``xplane.proto``: XSpace
    1 planes; XPlane 2 name, 3 lines, 4 event_metadata, 5 stat_metadata;
    XEventMetadata 2 name, 5 stats; XStatMetadata 1 id, 2 name; XStat 1
    metadata_id, 5 str_value, 7 ref_value), skipping the lines."""
    buf = memoryview(raw)
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(buf, *v))
                if 2 in entry:
                    md = dict(_fields(buf, *entry[2]))
                    stat_names[md.get(1, 0)] = _text(buf, md[2]) \
                        if 2 in md else ""
        m = profile.DEVICE_PLANE.match(name)
        if not m:
            continue
        want = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        names = out[int(m.group(1))] = {}
        for entry in metas:
            md = dict(_fields(buf, *entry)).get(2)
            if md is None:
                continue
            ev_name, op = "", None
            for f, v in _fields(buf, *md):
                if f == 2:
                    ev_name = _text(buf, v)
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) not in want:
                        continue
                    op = (_text(buf, stat[5]) if 5 in stat else
                          stat_names.get(stat.get(7), ""))
            if op is not None:
                names[ev_name] = op.rsplit(":", 1)[0]
    return out


def load_op_names(path) -> dict[int, dict[str, str]]:
    """:func:`op_names` of an ``.xplane.pb`` file, or of one gzipped.
    Raises :class:`NoOpNames` if no operation on a device plane has an
    ``op_name``."""
    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    names = op_names(raw)
    if not any(names.values()):
        raise NoOpNames(
            f"no operation on a device plane of {path} carries the stat "
            f"'{OP_NAME_STAT}' (its op_name), so device time cannot be "
            "attributed to the program's named scopes")
    return names


@dataclass
class ScopeTimes:
    """Self time by (phase, stage) inside one window, mean over devices."""
    seconds: dict[tuple[str, str], float]
    busy_s: float

    @property
    def scoped(self) -> bool:
        """Whether any operation ran under an ``hmmu.*`` scope (a program
        without the scopes has only ``other``)."""
        return any(p != OTHER for p, _ in self.seconds)

    def phase_s(self, *phases: str) -> float:
        return sum(v for (p, _), v in self.seconds.items() if p in phases)

    def groups_us(self, steps: int) -> dict[str, float]:
        """Microseconds per chunk step of each of ``GROUPS``: self time
        over ``steps``, the scan iterations times the design points, as
        ``device_us_per_chunk.run`` scales busy time. They add up to busy
        time per chunk step."""
        return {g: 1e6 * self.phase_s(*ph) / steps
                for g, ph in GROUPS.items()}

    def table(self, steps: int) -> list[str]:
        """One line per (phase, stage): microseconds per chunk step over
        ``steps`` steps, and the share of busy time. Every scope of
        ``PHASES`` and ``STAGES`` gets a line where the program has
        scopes, 0 where XLA fused its operations into another scope's."""
        keys = set(self.seconds)
        if self.scoped:
            keys |= {(p, s) for p in PHASES for s in STAGES.get(p, ("",))}
        rows = []
        for p, s in sorted(keys, key=_order):
            v = self.seconds.get((p, s), 0.0)
            share = 100.0 * v / self.busy_s if self.busy_s > 0 else 0.0
            rows.append(f"scope {p:14s} {s or '-':8s} "
                        f"{1e6 * v / steps:12.3f} us/chunk {share:7.3f}%")
        return rows


def _order(key):
    p, s = key
    stages = STAGES.get(p, ())
    return (PHASES.index(p) if p in PHASES else len(PHASES), p,
            stages.index(s) if s in stages else -1, s)


def reduce(ops: dict, names: dict, lo: float, hi: float) -> ScopeTimes:
    """Self time by scope inside the window ``[lo, hi)`` of the device
    operations ``profile.read_events`` returns, each named by its
    ``op_name`` in ``names`` (:func:`op_names`; '' where it has none):
    summed per device, then averaged over devices."""
    total: dict[tuple[str, str], float] = {}
    busy = []
    for dev in sorted(ops):
        events, st, en = ops[dev]
        cs, ce = np.clip(st, lo, hi), np.clip(en, lo, hi)
        keep = ce > cs
        own = self_times(cs[keep], ce[keep])
        busy.append(float(own.sum()))
        # Sum by event name first: a trace repeats a few hundred names.
        codes: dict[str, int] = {}
        code = np.asarray([codes.setdefault(n, len(codes)) for n in events],
                          np.int64)[keep]
        by_code = np.bincount(code, weights=own, minlength=len(codes))
        seen = np.bincount(code, minlength=len(codes)) > 0
        lookup = names.get(dev, {})
        for name, c in codes.items():
            if seen[c]:
                key = scope_of(lookup.get(name, ""))
                total[key] = total.get(key, 0.0) + float(by_code[c])
    n = len(busy)
    return ScopeTimes({k: v / n for k, v in total.items()}, sum(busy) / n)
