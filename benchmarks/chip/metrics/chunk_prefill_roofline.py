"""The paged chunked-prefill attention kernel's roofline share (%), over
the traced chunk-prefill calls, as for paged decode."""
from harness import costs


def read(ctx):
    return costs.kernel_share(ctx, "chunk", "jit_prefill_chunk",
                              costs.chunk_prefill)
