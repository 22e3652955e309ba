"""Share (%) of the compact decode programs' device time on the first chip
spent in collective operations (all-reduce, all-gather, reduce-scatter,
collective-permute, and their -start / -done halves): the exchange between
the chips that split each layer. Silent where the programs hold none."""
import bisect

from harness import trace as T

COLLECTIVES = {f"{op}{half}" for half in ("", "-start", "-done")
               for op in ("all-reduce", "all-gather", "reduce-scatter",
                          "collective-permute")}


def read(ctx):
    if ctx.trace is None:
        return None
    dev = ctx.devices[0]
    mods = T.modules(ctx.trace, dev, "jit_decode_compact")
    ops = ctx.trace.devices[dev].ops
    starts = [s for s, _, _ in ops]
    spent = coll = 0.0
    for ms, me, _ in mods:
        spent += me - ms
        for s, e, name in ops[bisect.bisect_left(starts, ms):
                              bisect.bisect_left(starts, me)]:
            if e <= me and T.short_op(name)[1] in COLLECTIVES:
                coll += e - s
    if not coll:
        return None
    return 100.0 * coll / spent
