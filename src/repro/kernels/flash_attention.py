"""Pallas TPU flash-attention (forward): blocked online-softmax with causal /
sliding-window / chunked-local masks and GQA head mapping.

TPU adaptation (DESIGN.md §6): the grid's innermost dim iterates KV blocks
*sequentially* on TPU, so the running (m, l, acc) state lives in VMEM scratch
across grid steps — no HBM round-trips for the softmax state. Block shapes
are MXU-aligned (multiples of 128 where dims allow). Fully-masked KV blocks
are skipped via pl.when on the block-level causal/window bounds.

Contiguous positions are assumed (qpos/kpos ascending); the mask refs still
make padding (-1) exact.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_ref, l_ref, acc_ref, *,
            scale: float, window: Optional[int], chunk: Optional[int],
            q_block: int, kv_block: int, nk: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip bounds (contiguous positions): block is live unless
    # entirely above the diagonal or entirely outside the window/chunk.
    q_lo = qi * q_block
    q_hi = q_lo + q_block - 1
    k_lo = ki * kv_block
    live = k_lo <= q_hi
    reach = window if window is not None else (chunk if chunk is not None else None)
    if reach is not None:
        k_hi = k_lo + kv_block - 1
        live &= k_hi >= q_lo - reach

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [qb, hd]
        k = k_ref[0, 0].astype(jnp.float32)                   # [kb, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        qp = qpos_ref[0]                                      # [qb, 1]
        kp = kpos_ref[0]                                      # [1, kb]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = (kp <= qp) & (kp >= 0)
        if window is not None:
            mask &= kp > qp - window
        if chunk is not None:
            mask &= (kp // chunk) == (qp // chunk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                   # [qb, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)
        # row log-sum-exp (saved for the backward kernels)
        lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _tile(s: int, block: int) -> int:
    """Block length for a sequence of length s: `block`, or s rounded up
    to the 128-lane tile when the whole sequence is shorter."""
    return min(block, -(-s // 128) * 128)


def flash_attention_fwd(q, k, v, qpos, kpos, *,
                        window: Optional[int] = None,
                        chunk: Optional[int] = None,
                        q_block: int = 512, kv_block: int = 512,
                        interpret: bool = False, return_lse: bool = False):
    """q [b,s,H,hd]; k/v [b,s,K,hd]; qpos/kpos [b,s] -> out [b,s,H,hd]
    (+ lse [b,s,H] when return_lse — consumed by flash_attention_bwd).

    The kernel runs head-major ([b, H, s, hd] tiles of (block, hd)) with
    query positions as a column and key positions as a row, so every
    block's trailing two dims are TPU tiles. A sequence that the blocks do
    not divide is padded with position -1 (masked) and cropped after."""
    b, s, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    q_block, kv_block = _tile(s, q_block), _tile(s, kv_block)
    s_pad = -(-s // math.lcm(q_block, kv_block)) * math.lcm(q_block, kv_block)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s))
        q, k, v = (jnp.pad(t, pad + ((0, 0), (0, 0))) for t in (q, k, v))
        qpos = jnp.pad(qpos, pad, constant_values=-1)
        kpos = jnp.pad(kpos, pad, constant_values=-1)
    nq, nk = s_pad // q_block, s_pad // kv_block
    grid = (b, H, nq, nk)
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, chunk=chunk,
        q_block=q_block, kv_block=kv_block, nk=nk)

    heads_major = lambda t: jnp.swapaxes(t, 1, 2)             # [b,h,s,hd]
    q_tile = pl.BlockSpec((1, 1, q_block, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_tile = pl.BlockSpec((1, 1, kv_block, hd),
                           lambda bi, hi, qi, ki: (bi, hi // G, ki, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, 1), lambda bi, hi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, kv_block), lambda bi, hi, qi, ki: (bi, 0, ki)),
            q_tile, kv_tile, kv_tile,
        ],
        out_specs=[
            q_tile,
            pl.BlockSpec((1, 1, q_block, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, s_pad, hd), q.dtype),
            jax.ShapeDtypeStruct((b, H, s_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),      # m
            pltpu.VMEM((q_block, 1), jnp.float32),      # l
            pltpu.VMEM((q_block, hd), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(qpos.astype(jnp.int32)[..., None], kpos.astype(jnp.int32)[:, None, :],
      heads_major(q), heads_major(k), heads_major(v))
    out = heads_major(out)[:, :s]
    if not return_lse:
        return out
    return out, jnp.swapaxes(lse[..., 0], 1, 2)[:, :s]
