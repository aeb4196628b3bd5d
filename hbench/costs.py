"""The least work a chunk step must do, counted from shapes alone.

The count is the same whatever implements the step, so a share of the
roofline built on it can only rise when the step gets faster.
"""
from __future__ import annotations

INT32 = 4
ROW_LANES = 8            # int32 lanes of a packed redirection-table row
TRACE_IN = 3 * INT32 + 1     # page, offset, size (int32) and is_write (bool)
# Per-request outputs of the emulation call: returns, device, latency,
# retired_page, tombstone (int32) and faulted (bool).
OUTS = 5 * INT32 + 1


def chunk_step_bytes(chunk: int) -> int:
    """Least HBM bytes one chunk step of one design point moves: the
    chunk's requests read in and its per-request outputs written out, one
    table row gathered per request plus the two rows of the in-flight
    swap, and each request's hotness counter read and written back at
    the boundary commit. The aging shift and the min-wear scrub are left
    out: a lazy implementation need not touch every page each period."""
    return (chunk * (TRACE_IN + OUTS)
            + (chunk + 2) * ROW_LANES * INT32
            + chunk * 2 * INT32)
