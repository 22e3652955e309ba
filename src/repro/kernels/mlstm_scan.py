"""Pallas TPU chunked mLSTM scan: the xLSTM hot loop.

TPU adaptation (DESIGN.md §6): the GPU reference implementations stream the
recurrence with warp-level primitives; on TPU we use the *chunkwise-parallel*
form — within a chunk everything is dense matmul work for the MXU (D-matrix
intra-chunk attention-like term), across chunks a compact (dk × dv) state
tile is carried in VMEM scratch over the sequentially-iterated chunk grid
dimension. Stabilized in log-space exactly like the per-step reference
(kernels/ref.py:mlstm_ref): the chunkwise max telescopes to the same m_t.

Grid: (batch*heads, n_chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _kernel(q_ref, k_ref, v_ref, i_ref, f_ref, c0_ref, n0_ref, m0_ref,
            o_ref, c_out_ref, n_out_ref, m_out_ref,
            c_ref, n_ref, m_ref, *,
            scale: float, nc: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = c0_ref[0]
        n_ref[...] = n0_ref[0][:, None]
        m_ref[0, 0] = m0_ref[0, 0]

    q = q_ref[0].astype(jnp.float32) * scale      # [c, dk]
    k = k_ref[0].astype(jnp.float32)              # [c, dk]
    v = v_ref[0].astype(jnp.float32)              # [c, dv]
    ig = i_ref[0, :].astype(jnp.float32)          # [c]
    fg = f_ref[0, :].astype(jnp.float32)          # [c]

    logf = jax.nn.log_sigmoid(fg)
    g = jnp.cumsum(logf)                          # inclusive cumulative decay
    m_prev = m_ref[0, 0]
    C_prev = c_ref[...]                           # [dk, dv]
    n_prev = n_ref[...]                           # [dk, 1]

    # Stabilizer per step t: m_t = max(m_prev + g_t, max_{s<=t}(g_t - g_s + i_s))
    dmat = g[:, None] - g[None, :] + ig[None, :]  # [c(t), c(s)]
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    dmat = jnp.where(tri, dmat, NEG_INF)
    m_intra = dmat.max(axis=1)
    m_t = jnp.maximum(m_prev + g, m_intra)

    # Intra-chunk (MXU): weights exp(D - m_t), scores q k^T.
    w = jnp.where(tri, jnp.exp(dmat - m_t[:, None]), 0.0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [c, c]
    sw = s * w
    out_intra = jax.lax.dot_general(sw, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    qn_intra = sw.sum(axis=1)

    # Inter-chunk from carried state.
    inter_coeff = jnp.exp(m_prev + g - m_t)       # [c]
    qC = jax.lax.dot_general(q, C_prev, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [c, dv]
    qn_inter = jax.lax.dot_general(q, n_prev, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)[:, 0]
    num = inter_coeff[:, None] * qC + out_intra
    qn = inter_coeff * qn_inter + qn_intra
    den = jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))
    o_ref[0] = (num / den[:, None]).astype(o_ref.dtype)

    # State update to end of chunk.
    g_end = g[chunk - 1]
    m_new = jnp.maximum(m_prev + g_end, jnp.max(g_end - g + ig))
    a = jnp.exp(g_end - g + ig - m_new)           # [c]
    decay = jnp.exp(m_prev + g_end - m_new)
    c_ref[...] = decay * C_prev + jax.lax.dot_general(
        k * a[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_ref[...] = decay * n_prev + jax.lax.dot_general(
        k * a[:, None], jnp.ones((chunk, 1), jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[0, 0] = m_new

    @pl.when(ci == nc - 1)
    def _finalize():
        c_out_ref[0] = c_ref[...]
        n_out_ref[0] = n_ref[..., 0]
        m_out_ref[0, 0] = m_ref[0, 0]


def mlstm_scan_fwd(q, k, v, i_gate, f_gate, *, chunk: int = 128,
                   interpret: bool = False, initial=None):
    """Chunked mLSTM over folded heads.

    q, k [bh, s, dk]; v [bh, s, dv]; i_gate/f_gate [bh, s].
    `initial` optionally seeds the carried state: (C0 [bh, dk, dv],
    n0 [bh, dk], m0 [bh, 1]) — a mid-prompt chunk continues a sequence
    whose earlier chunks already ran (serving chunked prefill).
    Returns (out [bh, s, dv], (C [bh, dk, dv], n [bh, dk], m [bh, 1])).
    """
    bh, s, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    scale = 1.0 / np.sqrt(dk)
    if initial is None:
        C0 = jnp.zeros((bh, dk, dv), jnp.float32)
        n0 = jnp.zeros((bh, dk), jnp.float32)
        m0 = jnp.full((bh, 1), NEG_INF, jnp.float32)
    else:
        C0, n0, m0 = (t.astype(jnp.float32) for t in initial)
    kernel = functools.partial(_kernel, scale=scale, nc=nc, chunk=chunk)
    out, C, n, m = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, dv), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk), lambda bi, ci: (bi, ci)),
            pl.BlockSpec((1, chunk), lambda bi, ci: (bi, ci)),
            pl.BlockSpec((1, dk, dv), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, dk), lambda bi, ci: (bi, 0)),
            pl.BlockSpec((1, 1), lambda bi, ci: (bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, dk, dv), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, dk), lambda bi, ci: (bi, 0)),
            pl.BlockSpec((1, 1), lambda bi, ci: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((dk, dv)),
            _scratch((dk, 1)),
            _scratch((1, 1)),
        ],
        interpret=interpret,
    )(q, k, v, i_gate, f_gate, C0, n0, m0)
    return out, (C, n, m)
