"""Decode steps' share of the chip's peak (%): the model operations of the
traced decode calls over the decode programs' device time times peak."""
from harness import costs
from harness import trace as T


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = [c for c in ctx.calls if c.kind == "decode"]
    mods = T.modules(ctx.trace, 0, "jit_decode_compact")
    if not calls or len(mods) != len(calls):
        return None
    flops = sum(costs.call_flops(ctx.ref, ctx.config, c) for c in calls)
    spent = sum(e - s for s, e, _ in mods) * 1e-9
    return 100.0 * flops / (spent * ctx.peaks["flops"])
