"""Design points x emulated requests completed in the window over the
window's host wall time (for one design point, requests per second).
Host clock, ending at ``block_until_ready``."""


def read(ctx):
    if ctx["kind"] not in ("stream", "sweep"):
        return None
    return ctx["work"]["work"] / ctx["work"]["wall"]
