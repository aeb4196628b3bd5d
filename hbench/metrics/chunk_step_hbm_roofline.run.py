"""Share of the HBM roofline the chunk step reaches: the least bytes its
chunk steps must move (hbench.costs) over the device busy time, against
the chip's HBM bandwidth (hbench/peaks.json). Percent."""
from hbench.costs import chunk_step_bytes


def read(ctx):
    if ctx["kind"] not in ("stream", "sweep"):
        return None
    p, w = ctx["profile"], ctx["work"]
    busy = sum(p.busy_s) / len(p.busy_s)
    if busy <= 0:
        return None
    moved = chunk_step_bytes(ctx["chunk"]) * w["chunks"] * w["points"]
    return 100.0 * moved / busy / ctx["peaks"]["hbm_bytes_per_s"]
