"""Pallas TPU fused paged flash-prefill: write a prompt chunk into the
paged KV pool THROUGH the block table and attend over history + chunk in
O(chunk x block) tiles — online softmax, no [chunk, context] score matrix.

This is the prefill sibling of decode_attention._paged_kernel. The jnp
chunk-append path (models.attention._chunk_append) first scatters the chunk
into the pool and then gathers every allocated block back into a contiguous
fp "virtual ring" before running dense SDPA — an O(chunk x context) f32
score matrix plus, for quantized pools, a dequantized fp copy of the whole
context. Neither transient exists here: grid cell (b, j) DMAs exactly one
physical block, merges the chunk rows that land in it (quantize-on-write:
int8/int4 encoding happens in-kernel, so quantized pools never see an fp
intermediate in HBM), and folds the block into the running (m, l, acc)
softmax state. `core.predictor.prefill_transient_bytes` prices exactly this
difference, which is how tiled-prefill plans buy more lanes at tight
budgets.

Grid: (batch, max_blocks) — logical blocks iterate sequentially (innermost)
so the VMEM softmax state carries across blocks and a block's write-merge
always precedes its own attend. Block tables ride in as scalar prefetch and
the index maps chase the indirection, identical to paged decode; the pool
leaves alias their outputs so unvisited physical blocks keep their contents.

Within-chunk causality needs no ordering tricks: every chunk row landing in
block j is merged before block j is attended, rows in later blocks have
strictly larger positions, and the (cpos <= qpos) mask orders everything.
Write hazards can't occur — a physical block is written by at most one lane
(block tables partition the pool; shared prefix blocks are read-only by the
engine's CoW rule), and unmapped table entries clamp to the scratch block
where the merge is predicated off (identity write-back).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (_dequant_rows, _lane_column,
                                            _scratch, paged_quant_of)

NEG_INF = -1e30
_QMAX = {"int8": 127.0, "int4": 7.0}
_MIB = 2 ** 20


def _vmem_limit(C: int, G: int, K: int, hd: int, q_bytes: int,
                kv_bytes: int) -> int:
    """Scoped-VMEM cap for one prefill grid cell: the double-buffered
    query / output / new-KV blocks and the f32 softmax scratch (m and l
    pad to 128 lanes), with half again for in-kernel temporaries. The
    default 16 MiB cap is too small for 256-row chunks of f32 queries;
    a v5e core has 128 MiB."""
    rows = C * G * K
    blocks = 2 * (2 * rows * hd * q_bytes + 2 * C * K * hd * kv_bytes)
    scratch = rows * hd * 4 + 2 * rows * 128 * 4
    need = int(1.5 * (blocks + scratch)) + 8 * _MIB
    return min(max(need, 16 * _MIB), 112 * _MIB)


def _quantize_rows(x, quant: str):
    """In-kernel mirror of models.attention.quantize_kv for one kv head:
    x [C, hd] f32 -> (codes f32 [C, hd] int-valued, scale [C, 1] f32).
    Per-row absmax so a row's encoding never depends on its neighbours —
    merging a chunk row into a half-full block can't requantize what's
    already there."""
    qmax = _QMAX[quant]
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    q = jnp.round(x / jnp.maximum(scale, 1e-30))
    return jnp.clip(q, -qmax, qmax), scale


def _pack_int4(codes):
    """codes [C, hd] f32 in [-8, 7] -> packed f32 [C, hd//2] holding uint8
    byte values (lo | hi << 4, offset +8) — same layout quantize_kv
    stores. Kept in f32 so the one-hot merge matmul stays exact. (Runs in
    interpret mode only, like the int4 unpack.)"""
    c, hd = codes.shape
    pair = (codes + 8.0).reshape(c, hd // 2, 2)
    return pair[..., 0] + pair[..., 1] * 16.0


def _dot(a, b, contract_b: int, precision=None):
    """2-D matmul with f32 accumulation, contracting a's last dim with
    b's dim `contract_b` (1 = a @ b.T)."""
    return jax.lax.dot_general(a, b, (((1,), (contract_b,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _gather_rows(sel, x):
    """One-hot row gather sel [bs, C] @ x [C, n] at full f32 precision, so
    the merge copies values (f32 scale stripes included) bit for bit."""
    return _dot(sel, x, 0, precision=jax.lax.Precision.HIGHEST)


def _prefill_kernel(tbl_ref, lyr_ref, qrow_ref, qcol_ref, qg_ref, q_ref,
                    kn_ref, vn_ref, pp_ref, kp_ref, vp_ref, *refs,
                    scale: float, window: Optional[int],
                    chunk_mask: Optional[int], nl: int, bs: int, n_kv: int,
                    quant: str):
    # refs layout (mirrors decode_attention: flags append, never reorder):
    #   [ks_ref, vs_ref]          when quant != "none" (scale stripes in)
    #   o_ref, pp_out, kp_out, vp_out
    #   [ks_out, vs_out]          when quant != "none"
    #   m_ref, l_ref, acc_ref     (VMEM scratch, per kv head)
    # Like paged decode, every contraction is a 2-D matmul over one kv
    # head; the chunk's query rows of head j arrive as [C*G, hd] (row
    # c*G + g), with their positions as the column qg [C*G, 1].
    i = 0
    ks_ref = vs_ref = ks_out = vs_out = None
    if quant != "none":
        ks_ref, vs_ref = refs[0], refs[1]
        i = 2
    o_ref, pp_out, kp_out, vp_out = refs[i:i + 4]
    i += 4
    if quant != "none":
        ks_out, vs_out = refs[i], refs[i + 1]
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

    mapped = tbl_ref[bi, li] >= 0
    qrow = qrow_ref[0]                               # [1, C] int32
    qcol = qcol_ref[0]                               # [C, 1]
    C = qcol.shape[0]

    # ---- phase A: merge the chunk rows that land in this logical block.
    # sel [bs, C] one-hot: slot s takes chunk row c iff c's position maps
    # to (block li, slot s); selT is the same relation as [C, bs].
    # Positions are distinct, so each slot matches at most one row and
    # the one-hot matmul is an exact gather.
    def slot_of(qp):
        hit = (qp >= 0) & (qp // bs == li) & mapped
        return jnp.where(hit, qp % bs, -1)

    sel = slot_of(qrow) == jax.lax.broadcasted_iota(jnp.int32, (bs, C), 0)
    selT = slot_of(qcol) == jax.lax.broadcasted_iota(jnp.int32, (C, bs), 1)
    written = jnp.any(sel, axis=1, keepdims=True)    # [bs, 1]
    written_row = jnp.any(selT, axis=0, keepdims=True)   # [1, bs]
    new_pos = jnp.max(jnp.where(selT, qcol, -1), axis=0, keepdims=True)
    merged_pos = jnp.where(written_row, new_pos, pp_ref[0])   # [1, bs]
    pp_out[0] = merged_pos
    self_ = sel.astype(f32)

    qg = qg_ref[0]                                   # [C*G, 1]
    mask = (merged_pos <= qg) & (merged_pos >= 0) & (qg >= 0)  # [C*G, bs]
    if window is not None:
        mask &= merged_pos > qg - window
    if chunk_mask is not None:
        mask &= (merged_pos // chunk_mask) == (qg // chunk_mask)

    if quant != "none":
        ks_all = ks_ref[0].astype(f32)               # [bs, K]
        vs_all = vs_ref[0].astype(f32)
        lane = jax.lax.broadcasted_iota(jnp.int32, ks_all.shape, 1)

    for j in range(n_kv):
        kn = kn_ref[0, :, j, :].astype(f32)          # [C, hd]
        vn = vn_ref[0, :, j, :].astype(f32)
        if quant == "none":
            # fp pool: cast through the pool dtype so the chunk's own keys
            # are attended exactly as a later reader would see them
            merged_k = jnp.where(written, _gather_rows(self_, kn).astype(
                kp_ref.dtype), kp_ref[0, :, j, :])   # [bs, hd]
            merged_v = jnp.where(written, _gather_rows(self_, vn).astype(
                vp_ref.dtype), vp_ref[0, :, j, :])
            kp_out[0, :, j, :] = merged_k
            vp_out[0, :, j, :] = merged_v
            kblk = merged_k.astype(f32)
            vblk = merged_v.astype(f32)
        else:
            # quantize-on-write: encode the chunk rows in-register, merge
            # the integer codes + scale stripes into the block, and attend
            # against the DEQUANTIZED merge — bit-for-bit what the pool
            # now stores, and no fp copy of the pool ever reaches HBM.
            blks = []
            for x, raw_ref, out_ref, sc_all in (
                    (kn, kp_ref, kp_out, ks_all), (vn, vp_ref, vp_out, vs_all)):
                codes, sc = _quantize_rows(x, quant)  # [C,hd], [C,1]
                if quant == "int4":
                    codes = _pack_int4(codes)         # [C, hd//2]
                raw = raw_ref[0, :, j, :]
                merged_raw = jnp.where(
                    written, _gather_rows(self_, codes).astype(jnp.int32)
                    .astype(raw.dtype), raw)
                merged_sc = jnp.where(written, _gather_rows(self_, sc),
                                      _lane_column(sc_all, j))   # [bs, 1]
                out_ref[0, :, j, :] = merged_raw
                blks.append(_dequant_rows(merged_raw, merged_sc, quant))
                if sc_all is ks_all:
                    ks_all = jnp.where(lane == j, merged_sc, ks_all)
                else:
                    vs_all = jnp.where(lane == j, merged_sc, vs_all)
            kblk, vblk = blks

        # ---- phase B: fold this (post-write) block into head j's online
        # softmax
        @pl.when(mapped)
        def _merge():
            qv = q_ref[0, j].astype(f32) * scale     # [C*G, hd]
            s = jnp.where(mask, _dot(qv, kblk, 1), NEG_INF)   # [C*G, bs]
            m_prev = m_ref[j]                        # [C*G, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[j] = acc_ref[j] * corr + _dot(p, vblk, 0)
            m_ref[j] = m_new

    if quant != "none":
        ks_out[0] = ks_all
        vs_out[0] = vs_all

    @pl.when(li == nl - 1)
    def _finalize():
        for j in range(n_kv):
            o_ref[0, j] = (acc_ref[j] / jnp.maximum(l_ref[j], 1e-30)
                           ).astype(o_ref.dtype)


def paged_prefill_attention_fwd(q, k_new, v_new, k_pool, v_pool, pool_pos,
                                block_tables, positions, *, layer,
                                window: Optional[int] = None,
                                chunk_mask: Optional[int] = None,
                                k_scales=None, v_scales=None,
                                interpret: bool = False):
    """Fused chunk write + causal flash attend through a paged KV pool.

    q [b,C,K,G,hd]; k_new/v_new [b,C,K,hd] fp; pools
    [layers,n_blocks,block,K,hd] bf16 — or int8 / uint8 (packed int4) with
    per-row f32 scales [layers,n_blocks,block,K] in `k_scales`/`v_scales`;
    pool_pos [layers,n_blocks,block]; block_tables [b,max_blocks] int32
    (-1 = unassigned); positions [b,C] int32 (-1 = padding rows of a short
    final chunk); layer: an int32 scalar.

    Returns (o [b,C,K,G,hd], pool_pos', k_pool', v_pool'[, k_scales',
    v_scales']) — the pool leaves are updated IN PLACE via
    input_output_aliases; physical blocks no table entry points at keep
    their contents. As with paged decode, the grid's KV extent is the
    table width, so trimmed tables shrink prefill work too.

    The pool is LAYER-STACKED, as paged decode takes it: the kernel reads
    and writes layer `layer`'s blocks where they lie, and every other
    layer's blocks keep their contents like unvisited ones."""
    b, C, K, G, hd = q.shape
    m_blocks = block_tables.shape[1]
    n_layers, n_blocks, bs = pool_pos.shape
    quant = paged_quant_of(k_pool)
    if quant != "none" and (k_scales is None or v_scales is None):
        raise ValueError(f"{quant} pool needs k_scales/v_scales")
    hd_s = k_pool.shape[-1]                  # stored width (hd // 2 for int4)
    scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(_prefill_kernel, scale=scale, window=window,
                               chunk_mask=chunk_mask, nl=m_blocks, bs=bs,
                               n_kv=K, quant=quant)

    def lane(ndim):
        return lambda bi, li, tbl, lyr: (bi,) + (0,) * (ndim - 1)

    def pool(ndim):
        return lambda bi, li, tbl, lyr: (
            (lyr[0], jnp.maximum(tbl[bi, li], 0)) + (0,) * (ndim - 2))

    positions = positions.astype(jnp.int32)
    # per kv head, the chunk's queries as rows c*G + g, and the positions
    # as a row, a column, and a column repeated per query row
    qh = jnp.swapaxes(q, 1, 2).reshape(b, K, C * G, hd)
    args = [block_tables.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1),
            positions.reshape(b, 1, C), positions.reshape(b, C, 1),
            jnp.repeat(positions, G, axis=1).reshape(b, C * G, 1),
            qh, k_new, v_new, pool_pos.reshape(n_layers, n_blocks, 1, bs),
            k_pool, v_pool]
    sq = pl.squeezed
    in_specs = [
        pl.BlockSpec((1, 1, C), lane(3)),
        pl.BlockSpec((1, C, 1), lane(3)),
        pl.BlockSpec((1, C * G, 1), lane(3)),
        pl.BlockSpec((1, K, C * G, hd), lane(4)),
        pl.BlockSpec((1, C, K, hd), lane(4)),
        pl.BlockSpec((1, C, K, hd), lane(4)),
        pl.BlockSpec((sq, 1, 1, bs), pool(4)),
        pl.BlockSpec((sq, 1, bs, K, hd_s), pool(5)),
        pl.BlockSpec((sq, 1, bs, K, hd_s), pool(5)),
    ]
    out_specs = [
        pl.BlockSpec((1, K, C * G, hd), lane(4)),
        pl.BlockSpec((sq, 1, 1, bs), pool(4)),
        pl.BlockSpec((sq, 1, bs, K, hd_s), pool(5)),
        pl.BlockSpec((sq, 1, bs, K, hd_s), pool(5)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, K, C * G, hd), q.dtype),
        jax.ShapeDtypeStruct((n_layers, n_blocks, 1, bs), pool_pos.dtype),
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    # alias indices COUNT the scalar-prefetch operands: block_tables and
    # layer are inputs 0 and 1, so pool_pos / k_pool / v_pool sit at
    # 8 / 9 / 10
    aliases = {8: 1, 9: 2, 10: 3}
    if quant != "none":
        in_specs += [pl.BlockSpec((sq, 1, bs, K), pool(4))] * 2
        out_specs += [pl.BlockSpec((sq, 1, bs, K), pool(4))] * 2
        args += [k_scales, v_scales]
        out_shape += [jax.ShapeDtypeStruct(k_scales.shape, k_scales.dtype),
                      jax.ShapeDtypeStruct(v_scales.shape, v_scales.dtype)]
        aliases.update({11: 4, 12: 5})
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            _scratch((K, C * G, 1)),
            _scratch((K, C * G, 1)),
            _scratch((K, C * G, hd)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            C, G, K, hd, q.dtype.itemsize, k_new.dtype.itemsize)),
        interpret=interpret,
    )(*args)
    o = jnp.swapaxes(out[0].reshape(b, K, C, G, hd), 1, 2)
    return (o, out[1].reshape(n_layers, n_blocks, bs)) + tuple(out[2:])
