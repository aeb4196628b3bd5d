"""Share of the serving window in which the device ran no operation.
Percent."""


def read(ctx):
    if ctx["kind"] != "serve_closed":
        return None
    return 100.0 * max(ctx["profile"].idle_share)
