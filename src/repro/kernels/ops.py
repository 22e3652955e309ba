"""jit'd wrappers around the Pallas kernels with backend dispatch.

Backends:
  pallas    — compiled pallas_call on a TPU; off-TPU, where no Pallas
              compiler exists, the same kernel body in the interpreter
  interpret — pallas_call(interpret=True): kernel body evaluated on CPU;
              used by the allclose test sweeps. Refused on a TPU.
  blocked   — memory-equivalent pure-jnp tiling (lax.scan) — what the CPU
              dry-run lowers, keeping the compile-visible memory footprint
              faithful to the kernel's
  ref       — kernels.ref oracles (small shapes only)

Default: pallas on TPU, blocked elsewhere (`select_backend`). Override per
call or with env REPRO_KERNEL_BACKEND.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention_bwd import flash_attention_bwd
from repro.kernels.decode_attention import (decode_attention_fwd,
                                            paged_decode_attention_fwd)
from repro.kernels.mlstm_scan import mlstm_scan_fwd
from repro.kernels.prefill_attention import paged_prefill_attention_fwd

NEG_INF = -1e30
BACKENDS = ("pallas", "interpret", "blocked", "ref")

_RECORDERS: list = []


@contextlib.contextmanager
def record_traces():
    """Count, while the block runs, which implementation each attention
    path was traced with: {(path, impl): times}, impl being "compiled" /
    "interpret" (Pallas) or "jnp". A step traces once per compile, so this
    names what the compiled steps run."""
    rec: collections.Counter = collections.Counter()
    _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        _RECORDERS.remove(rec)


def note_trace(path: str, impl: str) -> None:
    for rec in _RECORDERS:
        rec[(path, impl)] += 1


def select_backend(platform: str, requested: Optional[str] = None) -> str:
    """The kernel backend for a process on `platform` (jax.default_backend()
    naming): `requested` (the REPRO_KERNEL_BACKEND override) if given, else
    compiled Pallas on a TPU and the blocked jnp tiling elsewhere. Interpret
    mode is never chosen on a TPU: asking for it there is an error."""
    backend = requested or ("pallas" if platform == "tpu" else "blocked")
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if platform == "tpu" and backend == "interpret":
        raise ValueError("the Pallas interpreter is not run on a TPU; "
                         "use the compiled 'pallas' backend there")
    return backend


def default_backend() -> str:
    return select_backend(jax.default_backend(),
                          os.environ.get("REPRO_KERNEL_BACKEND") or None)


def _interpret(backend: str, path: str) -> bool:
    """pallas_call's `interpret` flag for a Pallas backend: compiled on a
    TPU, interpreted elsewhere (there is no Pallas compiler for the CPU)."""
    platform = jax.default_backend()
    backend = select_backend(platform, backend)
    if backend not in ("pallas", "interpret"):
        raise ValueError(f"backend {backend!r} runs no Pallas kernel")
    interpret = platform != "tpu"
    note_trace(path, "interpret" if interpret else "compiled")
    return interpret


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, qpos, kpos, *, window: Optional[int] = None,
                    chunk: Optional[int] = None, backend: Optional[str] = None,
                    q_block: int = 512, kv_block: int = 512):
    """q [b,s,K,G,hd]; k/v [b,s,K,hd] -> [b,s,K,G,hd]."""
    backend = backend or default_backend()
    b, s, K, G, hd = q.shape
    if backend == "ref":
        return kref.flash_attention_ref(q, k, v, qpos, kpos,
                                        window=window, chunk=chunk)
    if backend in ("pallas", "interpret"):
        qf = q.reshape(b, s, K * G, hd)
        out = flash_attention_fwd(
            qf, k, v, qpos, kpos, window=window, chunk=chunk,
            q_block=q_block, kv_block=kv_block,
            interpret=_interpret(backend, "prompt_prefill"))
        return out.reshape(b, s, K, G, hd)
    # blocked jnp fallback lives in models.attention (shared tiling logic)
    from repro.models import attention as mattn
    from repro.configs.base import BlockSpec
    blk = BlockSpec(window=window, chunk=chunk)
    set_ = mattn.AttnSettings(backend="blocked", q_block=q_block,
                              kv_block=kv_block)
    return mattn._seq_attention(q, k, v, qpos, kpos, blk, set_)


@functools.lru_cache(maxsize=64)
def _flash_vjp(window, chunk, q_block, kv_block, interpret, G):
    """custom_vjp flash attention over per-H-head tensors (KV pre-repeated);
    dk/dv are reduced back over the G q-heads sharing each KV head."""

    @jax.custom_vjp
    def fn(qh, kh, vh, qpos, kpos):
        return flash_attention_fwd(qh, kh, vh, qpos, kpos, window=window,
                                   chunk=chunk, q_block=q_block,
                                   kv_block=kv_block, interpret=interpret)

    def fwd(qh, kh, vh, qpos, kpos):
        out, lse = flash_attention_fwd(qh, kh, vh, qpos, kpos, window=window,
                                       chunk=chunk, q_block=q_block,
                                       kv_block=kv_block,
                                       interpret=interpret, return_lse=True)
        return out, (qh, kh, vh, out, lse, qpos, kpos)

    def bwd(res, do):
        qh, kh, vh, out, lse, qpos, kpos = res
        dq, dk, dv = flash_attention_bwd(
            qh, kh, vh, out, lse, do, qpos, kpos, window=window, chunk=chunk,
            q_block=q_block, kv_block=kv_block, interpret=interpret)
        return dq, dk, dv, None, None

    fn.defvjp(fwd, bwd)
    return fn


def flash_attention_trainable(q, k, v, qpos, kpos, *,
                              window: Optional[int] = None,
                              chunk: Optional[int] = None,
                              q_block: int = 512, kv_block: int = 512,
                              interpret: bool = False):
    """Differentiable pallas flash attention (fwd + dQ/dK/dV kernels).

    q [b,s,K,G,hd]; k/v [b,s,K,hd] -> [b,s,K,G,hd]. KV is repeated to H
    heads for the kernels; dk/dv sum back over each KV head's G q-heads.
    """
    b, s, K, G, hd = q.shape
    qh = q.reshape(b, s, K * G, hd)
    kh = jnp.repeat(k, G, axis=2)
    vh = jnp.repeat(v, G, axis=2)
    fn = _flash_vjp(window, chunk, min(q_block, s), min(kv_block, s),
                    interpret, G)
    out = fn(qh, kh, vh, qpos, kpos)
    return out.reshape(b, s, K, G, hd)


def decode_attention(q, k_cache, v_cache, cache_pos, positions, *,
                     window: Optional[int] = None, chunk: Optional[int] = None,
                     backend: Optional[str] = None, kv_block: int = 512):
    """q [b,K,G,hd]; caches [b,L,K,hd] -> [b,K,G,hd]."""
    backend = backend or default_backend()
    if backend in ("pallas", "interpret"):
        L = k_cache.shape[1]
        kv_block = min(kv_block, L)
        if L % kv_block:
            kv_block = L  # single block for ragged small caches
        return decode_attention_fwd(
            q, k_cache, v_cache, cache_pos, positions,
            window=window, chunk=chunk, kv_block=kv_block,
            interpret=_interpret(backend, "ring_decode"))
    return kref.decode_attention_ref(q, k_cache, v_cache, cache_pos,
                                     positions, window=window, chunk=chunk)


def paged_decode_attention(q, k_pool, v_pool, pool_pos, block_tables,
                           positions, *, layer,
                           window: Optional[int] = None,
                           chunk: Optional[int] = None,
                           backend: str = "pallas",
                           k_scales=None, v_scales=None,
                           return_mass: bool = False):
    """Decode through layer `layer` of a layer-stacked paged KV pool:
    q [b,K,G,hd]; pools [layers,n_blocks,block,K,hd] (bf16, int8, or
    uint8-packed int4 with per-row f32 `k_scales`/`v_scales`
    [layers,n_blocks,block,K]); pool_pos [layers,n_blocks,block];
    block_tables [b,max_blocks] (-1 = unassigned) -> [b,K,G,hd], or (out,
    mass [b,max_blocks]) with `return_mass`. Quantized pools are DMA'd and
    dequantized inside the kernel — no fp pool copy. There is no jnp twin
    here (the kernel IS the gather; the jnp path lives in
    models.attention): `backend` is "pallas" — compiled on a TPU,
    interpreted elsewhere — or "interpret" off-TPU."""
    return paged_decode_attention_fwd(
        q, k_pool, v_pool, pool_pos, block_tables, positions, layer=layer,
        window=window, chunk=chunk, k_scales=k_scales, v_scales=v_scales,
        return_mass=return_mass,
        interpret=_interpret(backend, "decode"))


def paged_prefill_attention(q, k_new, v_new, k_pool, v_pool, pool_pos,
                            block_tables, positions, *, layer,
                            window: Optional[int] = None,
                            chunk: Optional[int] = None,
                            backend: str = "pallas",
                            k_scales=None, v_scales=None):
    """Fused chunked prefill through layer `layer` of a layer-stacked
    paged KV pool: write the chunk's K/V into the pool via the block
    tables (quantize-on-write in-kernel for int8/int4 pools — no fp
    intermediate in HBM) and flash-attend the chunk's queries over
    history + chunk in O(chunk x block) tiles.

    q [b,C,K,G,hd]; k_new/v_new [b,C,K,hd]; pools
    [layers,n_blocks,block,K,hd] (bf16, int8, or uint8-packed int4 with f32
    `k_scales`/`v_scales`); pool_pos [layers,n_blocks,block]; block_tables
    [b,max_blocks]; positions [b,C] (-1 = padding). Returns (o, pool_pos',
    k_pool', v_pool'[, ks', vs']), the pool leaves updated in place. Like
    paged decode there is no jnp twin: the kernel IS the scatter + gather,
    and `backend` is "pallas" or "interpret"."""
    return paged_prefill_attention_fwd(
        q, k_new, v_new, k_pool, v_pool, pool_pos, block_tables, positions,
        layer=layer, window=window, chunk_mask=chunk, k_scales=k_scales,
        v_scales=v_scales, interpret=_interpret(backend, "chunk_prefill"))


# ---------------------------------------------------------------------------
# mLSTM chunked scan
# ---------------------------------------------------------------------------

def _mlstm_chunked_jnp(q, k, v, i_gate, f_gate, chunk: int, initial=None):
    """Blocked jnp mirror of the Pallas kernel: lax.scan over chunks.
    `initial` = (C0 [bh,dk,dv], n0 [bh,dk], m0 [bh]) continues a sequence
    mid-prompt (serving chunked prefill); None starts from scratch."""
    bh, s, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    f32 = jnp.float32
    scale = 1.0 / np.sqrt(dk)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))

    qs = jnp.moveaxis(q.reshape(bh, nc, chunk, dk), 1, 0).astype(f32) * scale
    ks = jnp.moveaxis(k.reshape(bh, nc, chunk, dk), 1, 0).astype(f32)
    vs = jnp.moveaxis(v.reshape(bh, nc, chunk, dv), 1, 0).astype(f32)
    igs = jnp.moveaxis(i_gate.reshape(bh, nc, chunk), 1, 0).astype(f32)
    fgs = jnp.moveaxis(f_gate.reshape(bh, nc, chunk), 1, 0).astype(f32)

    def body(carry, xs):
        C, n, m = carry                       # [bh,dk,dv],[bh,dk],[bh]
        qc, kc, vc, ic, fc = xs
        logf = jax.nn.log_sigmoid(fc)
        g = jnp.cumsum(logf, axis=-1)         # [bh, c]
        dmat = g[:, :, None] - g[:, None, :] + ic[:, None, :]
        dmat = jnp.where(tri[None], dmat, NEG_INF)
        m_t = jnp.maximum(m[:, None] + g, dmat.max(axis=-1))
        w = jnp.where(tri[None], jnp.exp(dmat - m_t[..., None]), 0.0)
        sc = jnp.einsum("btk,bsk->bts", qc, kc) * w
        out_intra = jnp.einsum("bts,bsv->btv", sc, vc)
        qn_intra = sc.sum(axis=-1)
        inter = jnp.exp(m[:, None] + g - m_t)
        qC = jnp.einsum("btk,bkv->btv", qc, C)
        qn_inter = jnp.einsum("btk,bk->bt", qc, n)
        num = inter[..., None] * qC + out_intra
        qn = inter * qn_inter + qn_intra
        den = jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))
        out = num / den[..., None]
        g_end = g[:, -1]
        m_new = jnp.maximum(m + g_end, (g_end[:, None] - g + ic).max(axis=-1))
        a = jnp.exp(g_end[:, None] - g + ic - m_new[:, None])
        decay = jnp.exp(m + g_end - m_new)
        C = decay[:, None, None] * C + jnp.einsum("bsk,bsv->bkv",
                                                  kc * a[..., None], vc)
        n = decay[:, None] * n + (kc * a[..., None]).sum(axis=1)
        return (C, n, m_new), out

    if initial is None:
        C0 = jnp.zeros((bh, dk, dv), f32)
        n0 = jnp.zeros((bh, dk), f32)
        m0 = jnp.full((bh,), NEG_INF, f32)
    else:
        C0, n0, m0 = (t.astype(f32) for t in initial)
    (C, n, m), outs = jax.lax.scan(body, (C0, n0, m0),
                                   (qs, ks, vs, igs, fgs))
    out = jnp.moveaxis(outs, 0, 1).reshape(bh, s, dv).astype(v.dtype)
    return out, (C, n, m[:, None])


def mlstm_scan(q, k, v, i_gate, f_gate, *, chunk: int = 128,
               backend: Optional[str] = None, initial=None):
    """q, k [b,s,h,dk]; v [b,s,h,dv]; gates [b,s,h].

    `initial` optionally continues a sequence mid-prompt from carried
    state (C [b,h,dk,dv], n [b,h,dk], m [b,h,1]) — serving chunked
    prefill; None is a fresh sequence.
    Returns (out [b,s,h,dv], state (C [b,h,dk,dv], n [b,h,dk], m [b,h,1])).
    """
    backend = backend or default_backend()
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape((b * h, s) + t.shape[3:])
    if backend == "ref":
        init = (None if initial is None
                else (initial[0], initial[1], initial[2][..., 0]))
        out, (C, n, m) = kref.mlstm_ref(q, k, v, i_gate, f_gate,
                                        initial_state=init)
        return out, (C, n, m[..., None])
    qf, kf, vf = fold(q), fold(k), fold(v)
    igf, fgf = fold(i_gate), fold(f_gate)
    init_f = (None if initial is None
              else (initial[0].reshape(b * h, dk, dv),
                    initial[1].reshape(b * h, dk),
                    initial[2].reshape(b * h, 1)))
    if backend in ("pallas", "interpret"):
        out, (C, n, m) = mlstm_scan_fwd(qf, kf, vf, igf, fgf, chunk=chunk,
                                        interpret=_interpret(backend,
                                                             "mlstm_scan"),
                                        initial=init_f)
    else:
        init_j = None if init_f is None else (init_f[0], init_f[1],
                                              init_f[2][:, 0])
        out, (C, n, m) = _mlstm_chunked_jnp(qf, kf, vf, igf, fgf, chunk,
                                            initial=init_j)
    out = jnp.moveaxis(out.reshape(b, h, s, dv), 1, 2)
    return out, (C.reshape(b, h, dk, dv), n.reshape(b, h, dk),
                 m.reshape(b, h, 1))


def mlstm_decode_step(q, k, v, i_gate, f_gate, state):
    """Single-token mLSTM update. q,k [b,h,dk]; v [b,h,dv]; gates [b,h];
    state (C, n, m[b,h,1]) -> (out [b,h,dv], new_state)."""
    C, n, m = state
    m = m[..., 0]
    f32 = jnp.float32
    dk = q.shape[-1]
    logf = jax.nn.log_sigmoid(f_gate.astype(f32))
    m_new = jnp.maximum(logf + m, i_gate.astype(f32))
    fp = jnp.exp(logf + m - m_new)
    ip = jnp.exp(i_gate.astype(f32) - m_new)
    C = fp[..., None, None] * C + ip[..., None, None] * (
        k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :])
    n = fp[..., None] * n + ip[..., None] * k.astype(f32)
    qs = q.astype(f32) / np.sqrt(dk)
    num = jnp.einsum("bhk,bhkv->bhv", qs, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", qs, n)),
                      jnp.exp(-m_new))
    out = (num / den[..., None]).astype(v.dtype)
    return out, (C, n, m_new[..., None])
