"""Pallas TPU decode attention: one query token per sequence against a
blocked ring-buffer KV cache, running log-sum-exp merge across KV blocks.

The same (m, l, acc) merge algebra is reused by the sequence-parallel decode
path (parallel/sp.py) to combine per-shard partial attentions — this kernel
is the single-device version of that schedule.

Grid: (batch, kv_blocks) — kv blocks iterate sequentially (innermost), the
softmax state lives in VMEM scratch. All heads are processed per grid cell
(q is tiny: [K, G, hd]).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _kernel(pos_ref, cpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            scale: float, window: Optional[int], chunk: Optional[int],
            nl: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale         # [K, G, hd]
    k = k_ref[0].astype(jnp.float32)                 # [Lb, K, hd]
    v = v_ref[0].astype(jnp.float32)
    pos = pos_ref[0, 0]                              # scalar
    cpos = cpos_ref[0, :]                            # [Lb]
    s = jnp.einsum("kgh,lkh->kgl", q, k)             # [K, G, Lb]
    mask = (cpos <= pos) & (cpos >= 0)
    if window is not None:
        mask &= cpos > pos - window
    if chunk is not None:
        mask &= (cpos // chunk) == (pos // chunk)
    s = jnp.where(mask[None, None, :], s, NEG_INF)
    m_prev = m_ref[...]                              # [K, G]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.where(mask[None, None, :], jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "kgl,lkh->kgh", p, v)
    m_ref[...] = m_new

    @pl.when(li == nl - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]).astype(o_ref.dtype)


def decode_attention_fwd(q, k_cache, v_cache, cache_pos, positions, *,
                         window: Optional[int] = None,
                         chunk: Optional[int] = None,
                         kv_block: int = 512, interpret: bool = False):
    """q [b,K,G,hd]; caches [b,L,K,hd]; cache_pos [b,L]; positions [b]."""
    b, K, G, hd = q.shape
    L = k_cache.shape[1]
    kv_block = min(kv_block, L)
    assert L % kv_block == 0, (L, kv_block)
    nl = L // kv_block
    scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               chunk=chunk, nl=nl)
    return pl.pallas_call(
        kernel,
        grid=(b, nl),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, li: (bi, 0)),
            pl.BlockSpec((1, kv_block), lambda bi, li: (bi, li)),
            pl.BlockSpec((1, K, G, hd), lambda bi, li: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kv_block, K, hd), lambda bi, li: (bi, li, 0, 0)),
            pl.BlockSpec((1, kv_block, K, hd), lambda bi, li: (bi, li, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), lambda bi, li: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, K, G, hd), q.dtype),
        scratch_shapes=[
            _scratch((K, G)),
            _scratch((K, G)),
            _scratch((K, G, hd)),
        ],
        interpret=interpret,
    )(positions.reshape(b, 1), cache_pos, q, k_cache, v_cache)


# ---------------------------------------------------------------------------
# Paged decode: read the KV pool through per-sequence block tables
# ---------------------------------------------------------------------------
#
# The pool stores KV in fixed-size position blocks shared across sequences
# ([n_blocks, block, K, hd] per layer, stacked over layers); each sequence
# maps logical block j to a physical block via its table row. The tables
# and the layer index ride in as SCALAR PREFETCH
# (pltpu.PrefetchScalarGridSpec) so the index_map itself can chase the
# indirection — grid cell (b, j) DMAs exactly the physical block sequence b
# needs, which is what makes decode traffic proportional to the blocks a
# sequence actually wrote instead of the pool-wide max context. Unassigned
# table entries (-1) clamp to physical block 0 (the serving engine's
# scratch block) and are masked out in-kernel.

def _lane_column(blk, j: int):
    """Column j of a [rows, K] block as [rows, 1] — a masked lane reduce,
    which the TPU lowers without a lane-to-sublane relayout."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.sum(jnp.where(lane == j, blk, 0.0), axis=1, keepdims=True)


def _dequant_rows(raw, scale_col, quant: str):
    """In-kernel dequant of one kv head of one pool block: raw [bs, hd]
    fp / int8, or [bs, hd//2] uint8 (packed nibbles, offset +8);
    scale_col [bs, 1] f32 per-token absmax scales -> f32 [bs, hd]. This is
    the fused path: the DMA moved quantized bytes; no fp pool ever exists.
    (The int4 nibble interleave runs in interpret mode only.)"""
    if quant == "none":
        return raw.astype(jnp.float32)
    if quant == "int8":
        return raw.astype(jnp.float32) * scale_col
    lo = (raw & 0xF).astype(jnp.int32) - 8           # elements 0, 2, 4, ...
    hi = (raw >> 4).astype(jnp.int32) - 8            # elements 1, 3, 5, ...
    bs, hd2 = raw.shape
    full = jnp.stack([lo, hi], axis=-1).reshape(bs, hd2 * 2)
    return full.astype(jnp.float32) * scale_col


def _block_mask(cpos, pos, window: Optional[int], chunk: Optional[int]):
    """Which slots of a pool block (cpos [1, bs]) query position `pos`
    attends: causal, written (pos >= 0), and inside the window / chunk."""
    mask = (cpos <= pos) & (cpos >= 0)
    if window is not None:
        mask &= cpos > pos - window
    if chunk is not None:
        mask &= (cpos // chunk) == (pos // chunk)
    return mask


def _paged_kernel(tbl_ref, pos_ref, lyr_ref, cpos_ref, q_ref, k_ref, v_ref,
                  *refs,
                  scale: float, window: Optional[int], chunk: Optional[int],
                  nl: int, n_kv: int, quant: str = "none",
                  mass: bool = False):
    # refs layout (flags append, never reorder):
    #   [ks_ref, vs_ref]  when quant != "none"   (per-row scale blocks)
    #   o_ref
    #   [bm_ref, bl_ref]  when mass              (per-block max / sumexp)
    #   m_ref, l_ref, acc_ref                     (VMEM scratch, per kv head)
    # Every contraction is a 2-D matmul over ONE kv head: q [G, hd] against
    # that head's rows [bs, hd] of the block, so the kernel needs no
    # batched einsum and no in-kernel relayout of the [bs, K, hd] block.
    i = 0
    ks_ref = vs_ref = bm_ref = bl_ref = None
    if quant != "none":
        ks_ref, vs_ref = refs[0], refs[1]
        i = 2
    o_ref = refs[i]
    i += 1
    if mass:
        bm_ref, bl_ref = refs[i], refs[i + 1]
        i += 2
    m_ref, l_ref, acc_ref = refs[i:i + 3]
    bi = pl.program_id(0)
    li = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if mass:
        # every grid cell owns its (bi, li) mass slot; unassigned blocks
        # report an empty block (max = -inf, sumexp = 0)
        bm_ref[...] = jnp.full_like(bm_ref, NEG_INF)
        bl_ref[...] = jnp.zeros_like(bl_ref)

    # an unassigned logical block (table entry -1) contributes nothing to
    # the softmax — skip its whole merge (its DMA clamps to scratch block
    # 0, but the compute is predicated off)
    @pl.when(tbl_ref[bi, li] >= 0)
    def _merge():
        mask = _block_mask(cpos_ref[0], pos_ref[bi], window, chunk)  # [1, bs]
        ks = None if ks_ref is None else ks_ref[0].astype(f32)   # [bs, K]
        vs = None if vs_ref is None else vs_ref[0].astype(f32)
        for j in range(n_kv):
            q = q_ref[0, j].astype(f32) * scale                  # [G, hd]
            k = _dequant_rows(k_ref[0, :, j, :],
                              None if ks is None else _lane_column(ks, j),
                              quant)                             # [bs, hd]
            v = _dequant_rows(v_ref[0, :, j, :],
                              None if vs is None else _lane_column(vs, j),
                              quant)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)  # [G, bs]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[j]                                    # [G, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[j] = acc_ref[j] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
            m_ref[j] = m_new
            if mass:
                # block-LOCAL softmax stats; combined across blocks outside
                # the kernel (log-sum-exp merge, same algebra as (m, l))
                bmax = s.max(axis=-1, keepdims=True)             # [G, 1]
                bm_ref[0, 0, j] = bmax
                bl_ref[0, 0, j] = jnp.where(
                    mask, jnp.exp(s - bmax), 0.0).sum(axis=-1, keepdims=True)

    @pl.when(li == nl - 1)
    def _finalize():
        for j in range(n_kv):
            o_ref[0, j] = (acc_ref[j] / jnp.maximum(l_ref[j], 1e-30)
                           ).astype(o_ref.dtype)


def paged_quant_of(k_pool) -> str:
    """Pool storage codec, read off the pool's own dtype (self-describing,
    mirroring models.attention.paged_quant_kind)."""
    if k_pool.dtype == jnp.int8:
        return "int8"
    if k_pool.dtype == jnp.uint8:
        return "int4"
    return "none"


def paged_decode_attention_fwd(q, k_pool, v_pool, pool_pos, block_tables,
                               positions, *, layer,
                               window: Optional[int] = None,
                               chunk: Optional[int] = None,
                               k_scales=None, v_scales=None,
                               return_mass: bool = False,
                               interpret: bool = False):
    """q [b,K,G,hd]; pools [layers,n_blocks,block,K,hd] bf16 — or int8 /
    uint8 (packed int4 nibbles) with per-row f32 scales
    [layers,n_blocks,block,K] in `k_scales`/`v_scales`; pool_pos
    [layers,n_blocks,block]; block_tables [b,max_blocks] int32 (-1 =
    unassigned); positions [b]; layer: an int32 scalar.

    The pool is LAYER-STACKED and the kernel reads layer `layer` where it
    lies: the index rides as scalar prefetch and the index maps address
    (layer, physical block), so no layer's pool is ever sliced out.

    The grid's KV extent is the TABLE width, not the pool-wide max-context
    block count: callers that trim tables to the blocks actually allocated
    (serving lane compaction does) shrink the grid — and the unassigned
    tail that remains is skipped by the in-kernel predicate — so decode
    work tracks what sequences wrote, not what they could write.

    Quantized pools are read DIRECTLY: the block-table DMA moves int8/int4
    bytes (plus the tiny scale stripe, chased by the same index map) and
    dequant happens in-kernel after the copy — no fp-dequantized pool is
    ever materialized.

    `return_mass=True` additionally returns per-logical-block attention
    mass [b, max_blocks] (softmax weight captured by each block, averaged
    over heads) — the serving engine's block-retention signal. Per-block
    (max, sumexp) stats come out of the kernel and are merged outside with
    the standard log-sum-exp algebra."""
    b, K, G, hd = q.shape
    m_blocks = block_tables.shape[1]
    n_layers, n_blocks, bs = pool_pos.shape
    quant = paged_quant_of(k_pool)
    if quant != "none" and (k_scales is None or v_scales is None):
        raise ValueError(f"{quant} pool needs k_scales/v_scales")
    hd_s = k_pool.shape[-1]                  # stored width (hd // 2 for int4)
    scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               chunk=chunk, nl=m_blocks, n_kv=K, quant=quant,
                               mass=return_mass)

    # index maps see the grid cell and the three scalar-prefetch operands
    # (block tables, decode positions, layer); payload blocks chase the
    # table inside the layer's slab of the stacked pool
    def pool_block(ndim):
        return lambda bi, li, tbl, pos, lyr: (
            (lyr[0], jnp.maximum(tbl[bi, li], 0)) + (0,) * (ndim - 2))

    def per_lane(bi, li, tbl, pos, lyr):
        return bi, 0, 0, 0

    # positions ride as [layers, n_blocks, 1, bs] so a block's row is a
    # full-dim (1, bs) tile instead of a sub-tile slice of [n_blocks, bs]
    sq = pl.squeezed
    in_specs = [
        pl.BlockSpec((sq, 1, 1, bs), pool_block(4)),
        pl.BlockSpec((1, K, G, hd), per_lane),
        pl.BlockSpec((sq, 1, bs, K, hd_s), pool_block(5)),
        pl.BlockSpec((sq, 1, bs, K, hd_s), pool_block(5)),
    ]
    args = [block_tables.astype(jnp.int32), positions.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1),
            pool_pos.reshape(n_layers, n_blocks, 1, bs), q, k_pool, v_pool]
    if quant != "none":
        # scale stripes chase the same block table as their payload
        in_specs += [pl.BlockSpec((sq, 1, bs, K), pool_block(4))] * 2
        args += [k_scales, v_scales]
    out_specs = [pl.BlockSpec((1, K, G, hd), per_lane)]
    out_shape = [jax.ShapeDtypeStruct((b, K, G, hd), q.dtype)]
    if return_mass:
        out_specs += [pl.BlockSpec(
            (1, 1, K, G, 1),
            lambda bi, li, tbl, pos, lyr: (bi, li, 0, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((b, m_blocks, K, G, 1),
                                           jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, m_blocks),
        in_specs=in_specs,
        out_specs=out_specs if return_mass else out_specs[0],
        scratch_shapes=[
            _scratch((K, G, 1)),
            _scratch((K, G, 1)),
            _scratch((K, G, hd)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if return_mass else out_shape[0],
        interpret=interpret,
    )(*args)
    if not return_mass:
        return out
    o, bm, bl = out[0], out[1][..., 0], out[2][..., 0]
    # merge block-local (max, sumexp) into each block's global softmax
    # share: w_j = l_j * exp(m_j - M); mass_j = w_j / sum w
    M = bm.max(axis=1, keepdims=True)                # [b, 1, K, G]
    w = bl * jnp.exp(bm - M)                         # [b, nl, K, G]
    mass = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-30)
    return o, mass.mean(axis=(2, 3))                 # [b, nl]
