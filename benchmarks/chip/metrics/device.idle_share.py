"""Share of the traced window (%) in which no operation ran on the device,
averaged over the devices the run placed its mesh on."""
from harness import trace as T


def read(ctx):
    if ctx.trace is None or not set(ctx.devices) <= set(ctx.trace.devices):
        return None
    return 100.0 * (1.0 - T.mean_busy_s(ctx.trace, ctx.devices)
                    / ctx.trace.window_s)
