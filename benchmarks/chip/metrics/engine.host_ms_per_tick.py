"""Host time per engine tick outside executor calls (ms), over the window:
the engine's own scheduling, admission and bookkeeping."""


def read(ctx):
    if not ctx.ticks:
        return None
    host = sum(t.t1 - t.t0 - t.exec_s for t in ctx.ticks)
    return 1e3 * host / len(ctx.ticks)
