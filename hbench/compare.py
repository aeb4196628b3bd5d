"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``reference.py``), number by number, each
with its limit.

Numbers compared, one line each in every run's result:

* ``outs_mismatch``: per-request outputs (``returns``, ``latency``,
  ``device``) of every call, warm-up and window, that differ from the
  reference. Exact: limit 0.
* ``state_mismatch``: integer entries of the final carried state (every
  table lane, the bank free times, the scalar registers, the integer
  counters) that differ. Exact: limit 0.
* ``float_counter_gap``: the widest relative gap of a float32 counter
  accumulator (bytes, latency sum, energy). The platform sums a chunk's
  floats in an order of its own, so these are compared by a limit set
  from readings (``PERF.md``).
* ``requests_uncounted``: requests sent whose completion the platform's
  request counters do not show. Exact: limit 0.
* ``window_compiles``: programs compiled inside the measured window.
  Limit 0.
"""
from __future__ import annotations

import numpy as np

from hbench import reference as ref

# Limits, each set between the readings of sound runs and of the control
# (PERF.md, section 2, gives the readings).
LIMITS = {"outs_mismatch": 0, "state_mismatch": 0,
          "float_counter_gap": 1e-5, "requests_uncounted": 0,
          "window_compiles": 0}
OUT_KEYS = ("returns", "latency", "device")


def program_state(state, i=None) -> dict:
    """The program's carried state (``repro.core.EmulatorState``, host
    arrays) as the flat view the reference keeps; ``i`` picks one design
    point of a stacked sweep state."""
    def g(x):
        x = np.asarray(x)
        return x if i is None else x[i]

    dma = state.dma
    view = {"table": g(state.table).astype(np.int64),
            "bank_free": g(state.bank_free).astype(np.int64),
            "clock_ptr": g(state.clock_ptr), "chunk_idx": g(state.chunk_idx),
            "dma_active": g(dma.active), "dma_page_a": g(dma.page_a),
            "dma_page_b": g(dma.page_b), "dma_start": g(dma.start),
            "swaps_done": g(dma.swaps_done), "clock": g(state.clock),
            "link_free_rx": g(state.link_free_rx),
            "link_free_tx": g(state.link_free_tx),
            "last_return": g(state.last_return),
            "rescue_page": g(state.rescue_page),
            "min_wear": g(state.min_wear),
            "fault_cursor": g(state.fault_cursor)}
    for k in ref.INT_COUNTERS + ref.FLOAT_COUNTERS:
        view[k] = g(getattr(state.counters, k))
    return view


def reference_state(st: ref.State) -> dict:
    view = {"table": st.table(), "bank_free": st.bank_free}
    view.update(st.s)
    view.update(st.c)
    return view


class Tally:
    """Accumulates the compared numbers over calls and design points."""

    def __init__(self):
        self.n = {"outs_mismatch": 0, "state_mismatch": 0,
                  "float_counter_gap": 0.0, "requests_uncounted": 0,
                  "window_compiles": 0}
        self.where: list[str] = []

    def outs(self, got: dict, want: dict, what: str) -> None:
        for k in OUT_KEYS:
            bad = int(np.sum(np.asarray(got[k], np.int64) != want[k]))
            if bad:
                self.where.append(f"{what} {k}: {bad} differ")
            self.n["outs_mismatch"] += bad

    def state(self, got: dict, want: dict, what: str) -> None:
        for k, w in want.items():
            g = got[k]
            if k in ref.FLOAT_COUNTERS:
                gap = abs(float(g) - float(w)) / max(abs(float(w)), 1.0)
                self.n["float_counter_gap"] = max(
                    self.n["float_counter_gap"], gap)
                continue
            bad = int(np.sum(np.asarray(g, np.int64) != np.asarray(w)))
            if bad:
                self.where.append(f"{what} {k}: {bad} differ")
            self.n["state_mismatch"] += bad

    def correct(self) -> bool:
        return all(self.n[k] <= LIMITS[k] for k in LIMITS)

    def checks(self) -> dict:
        """Each number beside its limit, for the result line."""
        return {k: {"value": self.n[k], "limit": LIMITS[k]} for k in LIMITS}
