"""Device time per chunk step: busy time of a device over the scan
iterations it ran times the design points it holds. Microseconds, mean
over the devices used."""


def read(ctx):
    if ctx["kind"] not in ("stream", "sweep"):
        return None
    p, w = ctx["profile"], ctx["work"]
    steps = w["chunks"] * w["points"]
    busy = sum(p.busy_s) / len(p.busy_s)
    return 1e6 * busy / steps if steps and busy > 0 else None
