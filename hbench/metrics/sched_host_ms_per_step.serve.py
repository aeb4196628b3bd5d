"""Host time per scheduling step: the span around
``ContinuousBatchingScheduler.step`` (admission, eviction, assembly,
dispatch, harvest). Milliseconds, mean over the window."""


def read(ctx):
    d = ctx["profile"].spans.get("sched.step", [])
    return 1e3 * sum(d) / len(d) if d else None
