"""Share of the window in which a device ran no operation, for the
single-run and sweep cells: the worst device. Percent."""


def read(ctx):
    if ctx["kind"] not in ("stream", "sweep"):
        return None
    return 100.0 * max(ctx["profile"].idle_share)
