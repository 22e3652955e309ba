"""The yardstick's arithmetic: chip peaks, and the operations and bytes that
one kernel call or one model step needs, computed from shapes.

Counts are of the work the algorithm needs, not of what an implementation
happens to do: padding rows, padded lanes and masked-out scores count
nothing, and a paged kernel's bytes are those of the blocks a lane holds.
A roofline share built on them can only understate the kernel.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

# One chip's published peaks, keyed by jax's Device.device_kind. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The table entry of a device kind; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, pk: Dict) -> float:
    """The least time the chip could take for the work."""
    return max(flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# attention kernels, per layer and per call
# ---------------------------------------------------------------------------

def paged_decode(lanes: Iterable[Tuple[int, int]], *, H: int, K: int,
                 hd: int, block: int, kv_bytes: int = 2,
                 act_bytes: int = 2) -> Tuple[float, float]:
    """One layer of paged decode attention. `lanes`: (position written this
    step, blocks in the lane's table) per active lane. Each query attends
    position + 1 keys (QK^T and PV: 4 * H * hd flops per key); each lane
    reads the keys, values and positions of its blocks and moves its query
    and output once."""
    flops = nbytes = 0.0
    per_block = block * (2 * K * hd * kv_bytes + 4)
    for pos, n_blocks in lanes:
        flops += 4.0 * H * hd * (pos + 1)
        nbytes += n_blocks * per_block + 2 * H * hd * act_bytes
    return flops, nbytes


def chunk_prefill(lanes: Iterable[Tuple[int, int]], *, H: int, K: int,
                  hd: int, block: int, kv_bytes: int = 2,
                  act_bytes: int = 2) -> Tuple[float, float]:
    """One layer of paged chunked-prefill attention. `lanes`: (absolute
    start position, valid chunk tokens) per lane. Query i of a chunk
    starting at s attends s + i + 1 keys. Each lane reads the blocks that
    hold its history (positions below s), reads its chunk's queries, keys
    and values, writes the chunk's keys and values into the pool and writes
    its output."""
    flops = nbytes = 0.0
    per_block = block * (2 * K * hd * kv_bytes + 4)
    for start, c in lanes:
        flops += 4.0 * H * hd * (c * start + c * (c + 1) / 2)
        hist = -(-start // block)
        nbytes += (hist * per_block
                   + c * (2 * H * hd * act_bytes          # q in, o out
                          + 2 * K * hd * act_bytes        # new k, v in
                          + K * hd * 2 * kv_bytes + 4))   # k, v, pos out
    return flops, nbytes


# ---------------------------------------------------------------------------
# model steps, per executor call (the call records of harness.serve)
# ---------------------------------------------------------------------------

def _tokens_flops(ref, config, start: int, c: int) -> float:
    """Model operations, without the head, of c consecutive tokens at
    positions start .. start + c - 1 (token i attends start + i + 1)."""
    a = ref.token_flops(config, 0, head=False)
    b = ref.token_flops(config, 1, head=False) - a
    return c * a + b * (c * start + c * (c + 1) / 2)


def call_flops(ref, config, call) -> float:
    """Model operations a decode, chunk or whole-prompt prefill call needs:
    the real lanes' tokens only, and the head only where a token is read
    from its logits (every decode lane, a chunk that ends its prompt, each
    whole prompt)."""
    head = ref.token_flops(config, 0, head=True) - ref.token_flops(
        config, 0, head=False)
    if call.kind == "decode":
        return sum(_tokens_flops(ref, config, pos, 1) + head
                   for pos, _ in call.work)
    if call.kind == "chunk":
        return sum(_tokens_flops(ref, config, s, c) + (head if f else 0.0)
                   for s, c, f in call.work)
    if call.kind == "prefill":
        return sum(_tokens_flops(ref, config, 0, p) + head
                   for p in call.work)
    return 0.0


PALLAS = 'custom_call_target="tpu_custom_call"'


def per_chip_dims(ref, config: Dict) -> Dict[str, int]:
    """The reference's sizes with a layer's query and KV heads divided over
    the chips that split the layer (`serving.model_parallel`, which the
    harness's build holds the mesh to): what one chip's attention kernel
    computes, since the program runs each kernel once per chip on its own
    KV-head shard."""
    m = dict(ref.dims(config))
    mp = config["serving"]["model_parallel"]
    if m["H"] % mp or m["K"] % mp:
        raise ValueError(f"{m['H']} query and {m['K']} KV heads do not "
                         f"divide over {mp} chips")
    m["H"] //= mp
    m["K"] //= mp
    return m


def kernel_share(ctx, kind: str, module: str, cost_fn) -> "float | None":
    """Roofline share (%) of the Pallas kernel inside the `module` program
    (the step the executor calls of `kind` run), over the calls made while
    tracing: the sum of each call's least time (per layer, from `cost_fn`,
    at one chip's share of the heads) over the sum of the kernel's device
    time on the first chip used. None when the trace holds no such call, or
    when the kernel events do not number one per layer per call."""
    from harness import trace as T
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = [c for c in ctx.calls if c.kind == kind]
    m = per_chip_dims(ctx.ref, ctx.config)
    events = T.ops_in(ctx.trace, ctx.devices[0], module, PALLAS)
    if not calls or len(events) != m["L"] * len(calls):
        return None
    least = 0.0
    for c in calls:
        work = [w[:2] for w in c.work]
        flops, nbytes = cost_fn(work, H=m["H"], K=m["K"], hd=m["hd"],
                                block=ctx.kv_block)
        least += m["L"] * roofline_seconds(flops, nbytes, ctx.peaks)
    spent = sum(e - s for s, e, _ in events) * 1e-9
    return 100.0 * least / spent
