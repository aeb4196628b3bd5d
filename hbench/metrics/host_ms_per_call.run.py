"""Host time per emulation call: the span the harness opens around each
``Engine.run`` / ``Engine.continue_sweep`` until it returns, before any
block (padding, dispatch, donation). Milliseconds, mean over the window."""


def read(ctx):
    d = [x for name in ("engine.run", "engine.continue_sweep")
         for x in ctx["profile"].spans.get(name, [])]
    return 1e3 * sum(d) / len(d) if d else None
