"""Device time per chunk step of the served stream: busy time over the
scan iterations the window's dispatches ran (padded buckets included).
Microseconds."""


def read(ctx):
    if ctx["kind"] != "serve_closed":
        return None
    p, w = ctx["profile"], ctx["work"]
    busy = sum(p.busy_s) / len(p.busy_s)
    return 1e6 * busy / w["chunks"] if w["chunks"] and busy > 0 else None
