"""Active lanes per decode call over the window (the engine's `useful`
counter over its `decode_ticks` counter)."""


def read(ctx):
    u0, u1 = ctx.counters["useful"]
    d0, d1 = ctx.counters["decode_ticks"]
    if d1 == d0:
        return None
    return (u1 - u0) / (d1 - d0)
