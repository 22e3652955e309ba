"""Pallas TPU flash-attention backward: dQ, dK, dV without materializing
the attention matrix.

Standard two-kernel schedule (TPU grids iterate the innermost dim
sequentially, so accumulators live in VMEM scratch):

  dQ kernel:    grid (b, H, nq, nk)  — dq accumulated over kv blocks
  dK/dV kernel: grid (b, H, nk, nq)  — dk, dv accumulated over q blocks

Both recompute p = exp(s − L) from the forward's saved row log-sum-exp L
(m + log l), and use D = rowsum(dO ⊙ O):

  dv += pᵀ dO
  dp  = dO Vᵀ
  ds  = p ⊙ (dp − D)
  dq += ds K · scale      dk += dsᵀ Q · scale
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


def _mask_block(qp, kp, window, chunk):
    mask = (kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
    if window is not None:
        mask &= kp[None, :] > qp[:, None] - window
    if chunk is not None:
        mask &= (kp[None, :] // chunk) == (qp[:, None] // chunk)
    return mask


def _dq_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               dvec_ref, dq_out_ref, dq_acc, *,
               scale, window, chunk, q_block, kv_block, nk):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_lo, q_hi = qi * q_block, qi * q_block + q_block - 1
    k_lo = ki * kv_block
    live = k_lo <= q_hi
    reach = window if window is not None else chunk
    if reach is not None:
        live &= k_lo + kv_block - 1 >= q_lo - reach

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        dvec = dvec_ref[0, :, 0]
        qp = qpos_ref[0, :]
        kp = kpos_ref[0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _mask_block(qp, kp, window, chunk)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None])
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _fin():
        dq_out_ref[0, :, 0, :] = dq_acc[...].astype(dq_out_ref.dtype)


def _dkv_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dvec_ref, dk_out_ref, dv_out_ref, dk_acc, dv_acc, *,
                scale, window, chunk, q_block, kv_block, nq):
    qi = pl.program_id(3)
    ki = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_lo = qi * q_block
    q_hi = q_lo + q_block - 1
    k_lo = ki * kv_block
    live = k_lo <= q_hi
    reach = window if window is not None else chunk
    if reach is not None:
        live &= k_lo + kv_block - 1 >= q_lo - reach

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        dvec = dvec_ref[0, :, 0]
        qp = qpos_ref[0, :]
        kp = kpos_ref[0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _mask_block(qp, kp, window, chunk)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)      # [qb, kb]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # [kb, hd]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec[:, None])
        # q was pre-scaled at load, so dsᵀ·q already carries the 1/√d factor
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # [kb, hd]

    @pl.when(qi == nq - 1)
    def _fin():
        dk_out_ref[0, :, 0, :] = dk_acc[...].astype(dk_out_ref.dtype)
        dv_out_ref[0, :, 0, :] = dv_acc[...].astype(dv_out_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, qpos, kpos, *,
                        window: Optional[int] = None,
                        chunk: Optional[int] = None,
                        q_block: int = 512, kv_block: int = 512,
                        interpret: bool = False):
    """q/do/out [b,s,H,hd]; k/v [b,s,H,hd] (pre-repeated per-head KV);
    lse [b,s,H]. Returns (dq, dk, dv) with dk/dv per H head."""
    b, s, H, hd = q.shape
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    assert s % q_block == 0 and s % kv_block == 0
    nq, nk = s // q_block, s // kv_block
    scale = 1.0 / np.sqrt(hd)
    dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)                                       # [b,s,H]

    common_specs = dict(
        qpos=pl.BlockSpec((1, q_block), lambda bi, hi, i, j: (bi, i)),
        kpos=pl.BlockSpec((1, kv_block), lambda bi, hi, i, j: (bi, j)),
        q=pl.BlockSpec((1, q_block, 1, hd),
                       lambda bi, hi, i, j: (bi, i, hi, 0)),
        k=pl.BlockSpec((1, kv_block, 1, hd),
                       lambda bi, hi, i, j: (bi, j, hi, 0)),
        v=pl.BlockSpec((1, kv_block, 1, hd),
                       lambda bi, hi, i, j: (bi, j, hi, 0)),
        do=pl.BlockSpec((1, q_block, 1, hd),
                        lambda bi, hi, i, j: (bi, i, hi, 0)),
        lse=pl.BlockSpec((1, q_block, 1), lambda bi, hi, i, j: (bi, i, hi)),
        dvec=pl.BlockSpec((1, q_block, 1), lambda bi, hi, i, j: (bi, i, hi)),
    )

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, window=window,
                          chunk=chunk, q_block=q_block, kv_block=kv_block,
                          nk=nk),
        grid=(b, H, nq, nk),
        in_specs=[common_specs["qpos"], common_specs["kpos"],
                  common_specs["q"], common_specs["k"], common_specs["v"],
                  common_specs["do"], common_specs["lse"],
                  common_specs["dvec"]],
        out_specs=pl.BlockSpec((1, q_block, 1, hd),
                               lambda bi, hi, i, j: (bi, i, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, H, hd), q.dtype),
        scratch_shapes=[_scratch((q_block, hd))],
        interpret=interpret,
    )(qpos, kpos, q, k, v, do, lse, dvec)

    # dK/dV: swap the roles — kv blocks outer, q blocks inner (sequential)
    kv_specs = dict(
        qpos=pl.BlockSpec((1, q_block), lambda bi, hi, j, i: (bi, i)),
        kpos=pl.BlockSpec((1, kv_block), lambda bi, hi, j, i: (bi, j)),
        q=pl.BlockSpec((1, q_block, 1, hd),
                       lambda bi, hi, j, i: (bi, i, hi, 0)),
        k=pl.BlockSpec((1, kv_block, 1, hd),
                       lambda bi, hi, j, i: (bi, j, hi, 0)),
        v=pl.BlockSpec((1, kv_block, 1, hd),
                       lambda bi, hi, j, i: (bi, j, hi, 0)),
        do=pl.BlockSpec((1, q_block, 1, hd),
                        lambda bi, hi, j, i: (bi, i, hi, 0)),
        lse=pl.BlockSpec((1, q_block, 1), lambda bi, hi, j, i: (bi, i, hi)),
        dvec=pl.BlockSpec((1, q_block, 1), lambda bi, hi, j, i: (bi, i, hi)),
    )
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, window=window,
                          chunk=chunk, q_block=q_block, kv_block=kv_block,
                          nq=nq),
        grid=(b, H, nk, nq),
        in_specs=[kv_specs["qpos"], kv_specs["kpos"], kv_specs["q"],
                  kv_specs["k"], kv_specs["v"], kv_specs["do"],
                  kv_specs["lse"], kv_specs["dvec"]],
        out_specs=[
            pl.BlockSpec((1, kv_block, 1, hd),
                         lambda bi, hi, j, i: (bi, j, hi, 0)),
            pl.BlockSpec((1, kv_block, 1, hd),
                         lambda bi, hi, j, i: (bi, j, hi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, s, H, hd), k.dtype),
                   jax.ShapeDtypeStruct((b, s, H, hd), v.dtype)],
        scratch_shapes=[_scratch((kv_block, hd)), _scratch((kv_block, hd))],
        interpret=interpret,
    )(qpos, kpos, q, k, v, do, lse, dvec)
    return dq, dk, dv
