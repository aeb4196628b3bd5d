"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

A trace holds one plane per device (``/device:TPU:<i>``) whose ``XLA Ops``
line lists every operation the device ran, and a host plane whose threads
carry the spans the harness opens with ``jax.profiler.TraceAnnotation``
around each call into the program (``SPANS``) and around the whole
measured window (``WINDOW``). Both are on one clock.

* busy time of a device: the union of its operation intervals inside the
  window;
* idle share: 1 - busy / window;
* top operations: total device time by XLA operation name;
* idle gaps: the stretches inside the window where a device ran nothing,
  each named by the harness span the host was in at the gap's middle.
"""
from __future__ import annotations

import glob
import gzip
import os
import pathlib
import re
from dataclasses import dataclass, field

import numpy as np

WINDOW = "window"
SPANS = ("engine.run", "engine.continue_sweep", "sched.step", "sched.submit",
         "window.block")
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Reduced:
    """What the metric readers get from one traced window."""
    window_s: float
    busy_s: list[float]                  # per device, inside the window
    spans: dict[str, list[float]]        # span name -> durations in s
    top_ops: list[list] = field(default_factory=list)
    idle_gaps: list[list] = field(default_factory=list)

    @property
    def idle_share(self) -> list[float]:
        return [1.0 - b / self.window_s for b in self.busy_s]


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the intervals ``[starts, ends)``."""
    if not len(starts):
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    # A new covered stretch begins wherever an interval starts past the
    # reach of every earlier one.
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    seg_id = np.cumsum(new) - 1
    seg_start = s[new]
    seg_end = np.zeros(len(seg_start))
    np.maximum.at(seg_end, seg_id, e)
    return float(np.sum(seg_end - seg_start))


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float):
    """The stretches of ``[lo, hi)`` no interval covers, as (start, end)."""
    if not len(starts):
        return [(lo, hi)]
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    out = []
    if s[0] > lo:
        out.append((lo, float(s[0])))
    between = s[1:] > e[:-1]
    out += [(float(a), float(b)) for a, b in zip(e[:-1][between],
                                                 s[1:][between])]
    if e[-1] < hi:
        out.append((float(e[-1]), hi))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path):
    """The profiler data of an ``.xplane.pb`` file, or of one gzipped."""
    from jax.profiler import ProfileData

    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def read_events(data):
    """(device ops, host spans) of profiler data (:func:`load`): ops as
    ``{device id: (names, start_s, end_s)}``, spans as ``[(name, start_s,
    end_s)]``; times in seconds on the trace's clock."""
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            names, st, en = [], [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    names.append(ev.name)
                    st.append(ev.start_ns)
                    en.append(ev.end_ns)
            ops[int(m.group(1))] = (names, np.asarray(st, float) * 1e-9,
                                    np.asarray(en, float) * 1e-9)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
    return ops, spans


def reduce(ops: dict, spans: list, top: int = 10) -> Reduced:
    """Reduce device ops and host spans (as :func:`read_events` returns
    them) to the window's busy times, span durations and breakdown."""
    win = [(a, b) for n, a, b in spans if n == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one '{WINDOW}' span, found {len(win)}")
    lo, hi = win[0]
    if not ops:
        raise ValueError("the trace holds no device plane")
    busy, per_op = [], {}
    worst = None
    for dev in sorted(ops):
        names, st, en = ops[dev]
        cs, ce = np.clip(st, lo, hi), np.clip(en, lo, hi)
        keep = ce > cs
        if len(st) and not keep.any():
            raise ValueError(f"no operation of device {dev} falls inside "
                             "the window: the trace's clocks disagree")
        busy.append(union_length(cs[keep], ce[keep]))
        for name, d, k in zip(names, ce - cs, keep):
            if k:
                per_op[name] = per_op.get(name, 0.0) + float(d)
        if worst is None or busy[-1] < worst[0]:
            worst = (busy[-1], cs[keep], ce[keep])
    n_dev = len(ops)
    top_ops = sorted(([k, v / n_dev] for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
    host = [(n, a, b) for n, a, b in spans if n in SPANS]
    idle = []
    for a, b in gaps(worst[1], worst[2], lo, hi):
        mid = 0.5 * (a + b)
        inside = [(sb - sa, n) for n, sa, sb in host if sa <= mid < sb]
        label = min(inside)[1] if inside else "outside spans"
        idle.append([label, b - a])
    idle.sort(key=lambda g: -g[1])
    durations: dict[str, list[float]] = {}
    for n, a, b in host:
        if lo <= a and b <= hi:
            durations.setdefault(n, []).append(b - a)
    return Reduced(window_s=hi - lo, busy_s=busy, spans=durations,
                   top_ops=top_ops, idle_gaps=idle[:top])
