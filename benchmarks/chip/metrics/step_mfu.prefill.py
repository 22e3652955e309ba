"""Prefill programs' share of the chips' peak (%): the model operations of
the traced chunked and whole-prompt prefill calls over those programs'
device time on the first chip times the peak of every chip the step ran
on."""
from harness import costs
from harness import trace as T


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = [c for c in ctx.calls if c.kind in ("chunk", "prefill")]
    dev = ctx.devices[0]
    mods = (T.modules(ctx.trace, dev, "jit_prefill_chunk")
            + T.modules(ctx.trace, dev, "jit_prefill_paged"))
    if not calls or len(mods) != len(calls):
        return None
    flops = sum(costs.call_flops(ctx.ref, ctx.config, c) for c in calls)
    spent = sum(e - s for s, e, _ in mods) * 1e-9
    return 100.0 * flops / (spent * ctx.peaks["flops"] * len(ctx.devices))
