"""Emulated memory requests the serving scheduler dispatched in the window
(all completed by its end) over the window's host wall time. Host clock,
ending at ``block_until_ready``."""


def read(ctx):
    if ctx["kind"] != "serve_closed":
        return None
    return ctx["work"]["work"] / ctx["work"]["wall"]
