"""The paged decode attention kernel's roofline share (%): for the traced
decode calls, the least time their attention needs (the keys and values of
each lane's blocks at HBM bandwidth, or its operations at peak) over the
kernel's device time."""
from harness import costs


def read(ctx):
    return costs.kernel_share(ctx, "decode", "jit_decode_compact",
                              costs.paged_decode)
