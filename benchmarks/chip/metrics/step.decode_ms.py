"""Device time per compact decode program (ms), on the first chip used."""
from harness import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    mods = T.modules(ctx.trace, ctx.devices[0], "jit_decode_compact")
    if not mods:
        return None
    return 1e3 * sum(e - s for s, e, _ in mods) * 1e-9 / len(mods)
