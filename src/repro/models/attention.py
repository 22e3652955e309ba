"""GQA attention with global / sliding-window / chunked-local modes.

Backends:
  naive   — full [s, s] score materialization (oracle; smoke shapes only).
  blocked — memory-efficient XLA-level tiling (the dry-run/default backend):
            * global causal: q-block × kv-block online-softmax scans
            * sliding window: exact per-q-block KV slices (linear memory)
            * chunked-local: chunks folded into batch, causal within chunk
  pallas  — the Pallas kernels (kernels.ops): flash attention for whole
            prompts, the paged decode / chunked-prefill kernels for the
            block pool. Compiled on a TPU, interpret-mode on CPU. The
            serving steps take it on a TPU (models.model.serving_settings).

Decode uses a unified ring-buffer KV cache: slot = position % cache_len with
absolute positions stored alongside for mask reconstruction — one layout
covers global, sliding-window and chunked layers (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import BlockSpec, ModelConfig
from repro.kernels import ops as kops
from repro.models import layers
from repro.parallel import axes as pax
from repro.parallel.axes import gather_fsdp, shard

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnSettings:
    backend: str = "blocked"     # naive | blocked | pallas
    q_block: int = 512
    kv_block: int = 1024
    # GQA head sharding for sequence paths: when kv_heads doesn't divide the
    # model axis but n_heads does, repeat K/V up to H heads so attention
    # shards by q-head instead of replicating across the axis (EXPERIMENTS
    # §Perf iteration 1: removes per-layer [b,s,d] all-gathers). None = auto.
    repeat_kv: Optional[bool] = None
    # ZeRO-3 gather-on-use: all-gather FSDP-sharded weights at each use
    # instead of psum-ing activation partials (§Perf iteration 2).
    gather_weights: bool = False
    # Paged decode: emit per-logical-block attention mass ([b, max_blocks],
    # softmax weight summed within each block, averaged over heads) in the
    # attn aux dict — the signal the serving engine's block-granular
    # retention policy (MemoryPlan.kv_retain) ranks blocks by.
    track_mass: bool = False


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "norm": layers.rmsnorm_init(d, dt),
        "wq": layers.dense_init(kq, d, cfg.n_heads * hd, dt),
        "wk": layers.dense_init(kk, d, cfg.n_kv_heads * hd, dt),
        "wv": layers.dense_init(kv, d, cfg.n_kv_heads * hd, dt),
        "wo": layers.dense_init(ko, cfg.n_heads * hd, d, dt),
    }


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

def _mask(qpos, kpos, blk: BlockSpec):
    """qpos [..., sq], kpos [..., skv] -> bool [..., sq, skv]."""
    q = qpos[..., :, None].astype(jnp.int32)
    k = kpos[..., None, :].astype(jnp.int32)
    m = (k <= q) & (k >= 0)
    if blk.window is not None:
        m &= k > q - blk.window
    if blk.chunk is not None:
        m &= (k // blk.chunk) == (q // blk.chunk)
    return m


# ---------------------------------------------------------------------------
# Pallas calls under a mesh
# ---------------------------------------------------------------------------

def _over_kv_heads(fn, args, head_axes, out_axes):
    """Call a Pallas kernel wrapper once per device on its KV-head shard.

    A pallas_call is a custom call the SPMD partitioner cannot split, so
    when the ambient mesh shards "kv_heads" the call runs under
    jax.shard_map: each array in `args` is split on its `head_axes` entry
    (None = replicated) and each output on its `out_axes` entry — None for
    an output every shard computes identically, "mean" for one averaged
    over heads (combined with a pmean). Without such a mesh this is just
    fn(*args)."""
    mesh = pax.current_mesh()
    axis = None
    if mesh is not None and not pax.annotations_suspended():
        n_kv = args[0].shape[head_axes[0]]
        axis = pax.logical_to_spec(("kv_heads",), mesh=mesh,
                                   shape=(n_kv,))[0]
    if axis is None:
        return fn(*args)
    single = not isinstance(out_axes, tuple)
    outs = (out_axes,) if single else out_axes

    def split(ax):
        return P() if ax in (None, "mean") else P(*([None] * ax + [axis]))

    def body(*local):
        with pax.suspend_annotations():
            res = fn(*local)
        res = (res,) if single else tuple(res)
        res = tuple(jax.lax.pmean(r, axis) if ax == "mean" else r
                    for r, ax in zip(res, outs))
        return res[0] if single else res

    out_specs = split(out_axes) if single else tuple(split(a) for a in outs)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=tuple(split(a) for a in head_axes),
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Sequence attention backends
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask):
    """q [b,sq,K,G,hd], k/v [b,skv,K,hd], mask [b,sq,skv] -> [b,sq,K,G,hd]."""
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    s = layers.einsum_f32("bqkgh,bskh->bkgqs", q, k) * scale
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = layers.einsum_f32("bkgqs,bskh->bqkgh", p, v)
    return o.astype(q.dtype)


def _naive(q, k, v, qpos, kpos, blk):
    return _sdpa(q, k, v, _mask(qpos, kpos, blk))


def _blocked_causal(q, k, v, qpos, kpos, blk: BlockSpec, set_: AttnSettings):
    """Online-softmax blocked causal attention (global layers)."""
    b, s, K, G, hd = q.shape
    qb = min(set_.q_block, s)
    kb = min(set_.kv_block, s)
    nq, nk = -(-s // qb), -(-s // kb)
    pad_q, pad_k = nq * qb - s, nk * kb - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad_k)), constant_values=-1)
    scale = 1.0 / np.sqrt(hd)

    qs = q.reshape(b, nq, qb, K, G, hd)
    qps = qpos.reshape(b, nq, qb)
    ks = k.reshape(b, nk, kb, K, hd)
    vs = v.reshape(b, nk, kb, K, hd)
    kps = kpos.reshape(b, nk, kb)

    def per_qblock(q_i, qp_i):
        # q_i [b, qb, K, G, hd]; scan over kv blocks with running (m, l, acc)
        m0 = jnp.full((b, qb, K, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, qb, K, G), jnp.float32)
        a0 = jnp.zeros((b, qb, K, G, hd), jnp.float32)

        @functools.partial(jax.checkpoint,
                           policy=jax.checkpoint_policies.nothing_saveable)
        def scan_body(carry, inp):
            m, l, acc = carry
            k_j, v_j, kp_j = inp
            sij = layers.einsum_f32("bqkgh,bskh->bqkgs", q_i, k_j) * scale
            msk = _mask(qp_i, kp_j, blk)
            sij = jnp.where(msk[:, :, None, None, :], sij, NEG_INF)
            m_new = jnp.maximum(m, sij.max(axis=-1))
            p = jnp.exp(sij - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + layers.einsum_f32(
                "bqkgs,bskh->bqkgh", p, v_j)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            scan_body, (m0, l0, a0),
            (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0),
             jnp.moveaxis(kps, 1, 0)))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_i.dtype)

    out = jax.lax.map(lambda args: jax.checkpoint(per_qblock)(*args),
                      (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(qps, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * qb, K, G, hd)
    return out[:, :s]


def _blocked_window(q, k, v, qpos, kpos, blk: BlockSpec, set_: AttnSettings):
    """Exact sliding-window attention: per-q-block KV slice of w + qb."""
    b, s, K, G, hd = q.shape
    w = blk.window
    qb = min(set_.q_block, s)
    nq = -(-s // qb)
    pad_q = nq * qb - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad_q)), constant_values=-1)
    # Left-pad KV by w so slice [i*qb, i*qb + w + qb) is always in range.
    kp = jnp.pad(k, ((0, 0), (w, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (w, 0), (0, 0), (0, 0)))
    kpp = jnp.pad(kpos, ((0, 0), (w, 0)), constant_values=-1)
    span = w + qb

    @jax.checkpoint  # flash-style backward: recompute probs per q-block
    def per_qblock(i):
        q_i = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        qp_i = jax.lax.dynamic_slice_in_dim(qpos, i * qb, qb, axis=1)
        k_i = jax.lax.dynamic_slice_in_dim(kp, i * qb, span, axis=1)
        v_i = jax.lax.dynamic_slice_in_dim(vp, i * qb, span, axis=1)
        kp_i = jax.lax.dynamic_slice_in_dim(kpp, i * qb, span, axis=1)
        return _sdpa(q_i, k_i, v_i, _mask(qp_i, kp_i, blk))

    out = jax.lax.map(per_qblock, jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * qb, K, G, hd)
    return out[:, :s]


def _chunked(q, k, v, qpos, kpos, blk: BlockSpec, set_: AttnSettings):
    """Chunked-local attention: fold chunks into batch, causal within."""
    b, s, K, G, hd = q.shape
    c = blk.chunk
    if s <= c:
        return _blocked_causal(q, k, v, qpos, kpos,
                               dataclasses.replace(blk, chunk=None), set_)
    pad = (-s) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
        out = _chunked(q, k, v, qpos, kpos, blk, set_)
        return out[:, :s]
    nc = s // c
    fold = lambda t: t.reshape((b * nc, c) + t.shape[2:])
    out = _blocked_causal(fold(q), fold(k), fold(v), fold(qpos), fold(kpos),
                          dataclasses.replace(blk, chunk=None), set_)
    return out.reshape(b, s, K, G, hd)


def _seq_attention(q, k, v, qpos, kpos, blk, set_: AttnSettings):
    if set_.backend == "pallas":
        return _over_kv_heads(
            functools.partial(kops.flash_attention, window=blk.window,
                              chunk=blk.chunk, backend="pallas"),
            (q, k, v, qpos, kpos), (2, 2, 2, None, None), 2)
    kops.note_trace("prompt_prefill", "jnp")
    if set_.backend == "naive":
        return _naive(q, k, v, qpos, kpos, blk)
    if blk.window is not None:
        return _blocked_window(q, k, v, qpos, kpos, blk, set_)
    if blk.chunk is not None:
        return _chunked(q, k, v, qpos, kpos, blk, set_)
    return _blocked_causal(q, k, v, qpos, kpos, blk, set_)


# ---------------------------------------------------------------------------
# Ring-buffer KV cache
# ---------------------------------------------------------------------------

def cache_init(cfg: ModelConfig, blk: BlockSpec, batch: int, context: int,
               dtype=jnp.bfloat16):
    L = blk.cache_len(context)
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, L, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, L, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.full((batch, L), -1, jnp.int32),
    }


def cache_spec(cfg: ModelConfig, blk: BlockSpec, batch: int, context: int,
               dtype=jnp.bfloat16):
    """ShapeDtypeStruct version of cache_init (dry-run, no allocation)."""
    L = blk.cache_len(context)
    hd = cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, L, cfg.n_kv_heads, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, L, cfg.n_kv_heads, hd), dtype),
        "pos": jax.ShapeDtypeStruct((batch, L), jnp.int32),
    }


def _cache_from_prefill(k, v, positions, blk: BlockSpec, context: int):
    """Build a ring cache holding the last cache_len positions of a prefill.

    Always emits the FULL cache_len(context) ring: a prompt shorter than the
    ring pads the empty slots with pos=-1 (masked). Without the padding a
    short-prompt prefill would hand decode a ring of length prompt_len whose
    slot = pos % prompt_len mapping evicts live context early (a global
    layer's ring must only wrap at cache_len); it also gives every sequence
    the same cache shapes, which is what lets the serving engine write any
    prefill into a pool slot (runtime.serve_step.write_cache_slot)."""
    L = blk.cache_len(context)
    k_t, v_t, p_t = k[:, -L:], v[:, -L:], positions[:, -L:]
    pad = L - k_t.shape[1]
    if pad > 0:
        # prefill positions start at 0, so occupied slots are already at
        # pos % L = 0..p-1; empty tail slots stay invalid
        k_t = jnp.pad(k_t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_t = jnp.pad(v_t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        p_t = jnp.pad(p_t, ((0, 0), (0, pad)), constant_values=-1)
    # Ring layout: slot = pos % L. For contiguous positions that's a roll.
    shift = p_t[0, 0] % L  # uniform across batch (packed sequences)
    return {
        "k": jnp.roll(k_t, shift, axis=1),
        "v": jnp.roll(v_t, shift, axis=1),
        "pos": jnp.roll(p_t, shift, axis=1),
    }


def _decode_attend(q, cache, blk: BlockSpec, positions,
                   return_probs: bool = False):
    """q [b,1,K,G,hd], cache k/v [b,L,K,hd]; positions [b]."""
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    s = layers.einsum_f32("bqkgh,bskh->bkgqs", q, cache["k"]) * scale
    msk = _mask(positions[:, None], cache["pos"], blk)   # [b, 1, L]
    s = jnp.where(msk[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = layers.einsum_f32("bkgqs,bskh->bqkgh", p, cache["v"])
    if return_probs:
        return o.astype(q.dtype), p
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV pool (serving decode)
# ---------------------------------------------------------------------------
#
# A paged layer cache is {"kb": [n_blocks, block, K, hd], "vb": ...,
# "pos": [n_blocks, block]} — storage is a POOL of fixed-size position
# blocks shared by every sequence, and each sequence's logical layout is a
# block table [max_blocks] mapping logical block j (positions j*block ..
# (j+1)*block - 1) to a physical block id (-1 = not yet allocated).
# Physical block 0 is the SCRATCH block: inactive decode lanes (table all
# -1) read and write it harmlessly, so one batched decode serves any pool
# occupancy with a single compile. Only full-context layers page; short
# windowed/chunked rings stay per-lane (see runtime.serve_step).
#
# QUANTIZED pools (MemoryPlan.kv_quant) additionally carry per-token
# per-head f32 absmax scales {"ks": [n_blocks, block, K], "vs": ...}; the
# pool is SELF-DESCRIBING — kb dtype int8 => "int8", uint8 => "int4"
# (two nibbles per byte, offset +8) — so every read/write path picks the
# codec from the cache itself and can never disagree with the layout
# init_paged_pool allocated. Scales are per-token rows, so appending a
# token to a block never rescales entries already written (block-granular
# absmax would force a lossy requantize on every tail write).

KV_QUANT_MAX = {"int8": 127.0, "int4": 7.0}


def paged_quant_kind(cache) -> str:
    """Storage codec of a paged layer cache, read off its own leaves."""
    if "ks" not in cache:
        return "none"
    return "int8" if cache["kb"].dtype == jnp.int8 else "int4"


def quantize_kv(x, kind: str):
    """Encode K/V rows for pool storage: x [..., hd] fp ->
    (q [..., hd] int8 | [..., hd//2] uint8, scale [...] f32). Per-row
    (token, head) absmax scales: |dequant - x| <= scale / 2 per element."""
    if kind == "none":
        return x, None
    qmax = KV_QUANT_MAX[kind]
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / qmax
    q = jnp.round(xf / jnp.maximum(scale, 1e-30)[..., None])
    q = jnp.clip(q, -qmax, qmax)
    if kind == "int8":
        return q.astype(jnp.int8), scale
    nib = (q + 8.0).astype(jnp.uint8)            # 1..15 (0 unused)
    lo, hi = nib[..., 0::2], nib[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8), scale


def dequantize_kv(q, scale, kind: str, dtype=jnp.bfloat16):
    """Decode pool-stored K/V rows back to fp (inverse of quantize_kv)."""
    if kind == "none":
        return q
    if kind == "int8":
        return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
    lo = (q & 0xF).astype(jnp.int32) - 8
    hi = (q >> 4).astype(jnp.int32) - 8
    full = jnp.stack([lo, hi], axis=-1).reshape(*q.shape[:-1],
                                                q.shape[-1] * 2)
    return (full.astype(jnp.float32) * scale[..., None]).astype(dtype)


def is_paged_cache(cache) -> bool:
    return isinstance(cache, dict) and "kb" in cache


def _paged_write(cache, block_tables, k1, v1, pos1):
    """Write one token per lane (k1/v1 [b,K,hd], pos1 [b]) into the pool at
    (table[pos // block], pos % block). Lanes with no block mapped (table
    entry -1) and INERT lanes (pos1 < 0 — a padding row the engine carries
    at full decode width while the lane is empty or mid-chunk-prefill) land
    in the scratch block with pos -1, so they can never clobber live KV."""
    n_blocks, bsz = cache["pos"].shape
    m_blocks = block_tables.shape[1]
    live = pos1 >= 0
    safe_pos = jnp.where(live, pos1, 0)
    lb = jnp.minimum(safe_pos // bsz, m_blocks - 1)
    off = safe_pos % bsz
    phys = jnp.take_along_axis(block_tables, lb[:, None], axis=1)[:, 0]
    phys = jnp.where(live & (phys >= 0), phys, 0)        # scratch fallback
    kind = paged_quant_kind(cache)
    kq, ks = quantize_kv(k1, kind)
    vq, vs = quantize_kv(v1, kind)
    out = {
        "kb": cache["kb"].at[phys, off].set(kq.astype(cache["kb"].dtype)),
        "vb": cache["vb"].at[phys, off].set(vq.astype(cache["vb"].dtype)),
        "pos": cache["pos"].at[phys, off].set(jnp.where(live, pos1, -1)),
    }
    if kind != "none":
        out["ks"] = cache["ks"].at[phys, off].set(ks)
        out["vs"] = cache["vs"].at[phys, off].set(vs)
    return out


def _paged_gather(cache, block_tables):
    """Gather each lane's blocks into a contiguous virtual ring
    ([b, max_blocks*block, ...]): unassigned table entries read the scratch
    block with their positions masked to -1, so downstream masking treats
    them as empty slots."""
    b, m_blocks = block_tables.shape
    bsz = cache["pos"].shape[1]
    safe = jnp.where(block_tables >= 0, block_tables, 0)
    pos = jnp.where(block_tables[..., None] >= 0, cache["pos"][safe], -1)
    kind = paged_quant_kind(cache)
    k, v = cache["kb"][safe], cache["vb"][safe]  # [b, mB, bs, K, hd']
    if kind != "none":
        k = dequantize_kv(k, cache["ks"][safe], kind)
        v = dequantize_kv(v, cache["vs"][safe], kind)
    return {
        "k": k.reshape(b, m_blocks * bsz, *k.shape[3:]),
        "v": v.reshape(b, m_blocks * bsz, *v.shape[3:]),
        "pos": pos.reshape(b, m_blocks * bsz),
    }


def _paged_write_chunk(cache, block_tables, k, v, positions):
    """Write a prompt chunk per lane (k/v [b, C, K, hd], positions [b, C],
    -1 = padding) into the pool through the block tables. Padding entries
    and entries whose logical block is unmapped land in the scratch block
    with pos -1, so nothing real can be clobbered and nothing stale can
    pass the mask."""
    n_blocks, bsz = cache["pos"].shape
    m_blocks = block_tables.shape[1]
    valid = positions >= 0
    safe_pos = jnp.where(valid, positions, 0)
    lb = jnp.clip(safe_pos // bsz, 0, m_blocks - 1)          # [b, C]
    phys = jnp.take_along_axis(block_tables, lb, axis=1)
    phys = jnp.where(valid & (phys >= 0), phys, 0)           # scratch
    off = safe_pos % bsz
    kind = paged_quant_kind(cache)
    kq, ks = quantize_kv(k, kind)
    vq, vs = quantize_kv(v, kind)
    out = {
        "kb": cache["kb"].at[phys, off].set(kq.astype(cache["kb"].dtype)),
        "vb": cache["vb"].at[phys, off].set(vq.astype(cache["vb"].dtype)),
        "pos": cache["pos"].at[phys, off].set(
            jnp.where(valid, positions, -1)),
    }
    if kind != "none":
        out["ks"] = cache["ks"].at[phys, off].set(ks)
        out["vs"] = cache["vs"].at[phys, off].set(vs)
    return out


def _chunk_append(q, k, v, cache, blk: BlockSpec, positions, block_tables,
                  settings: AttnSettings = AttnSettings()):
    """Chunked prefill: append a prompt chunk to an EXISTING cache and
    attend over history + chunk — exactly the chunk's slice of a full
    prefill, so interleaving chunks with decode ticks changes scheduling
    but never tokens. Paged layers go through the fused flash-prefill
    kernel when settings.backend == "pallas" (write + attend in one pass,
    O(chunk x block) tiles, quantize-on-write in-kernel) and otherwise
    scatter through the block table and attend over the gathered virtual
    ring (the jnp oracle: O(chunk x context) scores plus, for quantized
    pools, a dequantized fp copy of the context — the transient the tiled
    kernel exists to avoid); per-lane rings attend over concat(ring,
    chunk) and then keep only the last cache_len positions (slot = pos % L
    stays collision-free because the kept span is at most L consecutive
    positions)."""
    b, C = positions.shape
    valid = positions >= 0
    if is_paged_cache(cache):
        assert block_tables is not None, \
            "paged cache needs block_tables for chunked prefill"
        if settings.backend == "pallas":
            quant = paged_quant_kind(cache)
            scales = ((cache["ks"], cache["vs"]) if quant != "none" else ())

            def prefill(q, k, v, kb, vb, pos, tables, qpos, *scales):
                ks, vs = scales or (None, None)
                return kops.paged_prefill_attention(
                    q, k, v, kb, vb, pos, tables, qpos, window=blk.window,
                    chunk=blk.chunk, backend="pallas", k_scales=ks,
                    v_scales=vs)

            out = _over_kv_heads(
                prefill, (q, k, v, cache["kb"], cache["vb"], cache["pos"],
                          block_tables, positions) + scales,
                (2, 2, 2, 2, 2, None, None, None) + (2,) * len(scales),
                (2, None, 2, 2) + (2,) * len(scales))
            o, ppos, kb, vb = out[:4]
            new_cache = {"kb": kb, "vb": vb, "pos": ppos}
            if quant != "none":
                new_cache["ks"], new_cache["vs"] = out[4], out[5]
            return o, new_cache
        kops.note_trace("chunk_prefill", "jnp")
        new_cache = _paged_write_chunk(cache, block_tables, k, v, positions)
        virt = _paged_gather(new_cache, block_tables)
        o = _sdpa(q, virt["k"], virt["v"],
                  _mask(positions, virt["pos"], blk))
        return o, new_cache
    L = cache["pos"].shape[1]
    kcat = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], axis=1)
    vcat = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], axis=1)
    pcat = jnp.concatenate([cache["pos"], jnp.where(valid, positions, -1)],
                           axis=1)
    o = _sdpa(q, kcat, vcat, _mask(positions, pcat, blk))
    # ring write-back: only positions inside the final window survive
    # (a chunk longer than the ring would otherwise wrap onto itself)
    row_end = jnp.max(jnp.where(valid, positions, -1), axis=1, keepdims=True)
    keep = valid & (positions > row_end - L)
    slot = jnp.where(keep, positions % L, L)                 # L -> dropped
    bidx = jnp.arange(b)[:, None]
    new_cache = {
        "k": cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype),
                                           mode="drop"),
        "v": cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype),
                                           mode="drop"),
        "pos": cache["pos"].at[bidx, slot].set(positions, mode="drop"),
    }
    return o, new_cache


def _paged_decode(q, cache, blk: BlockSpec, pos1, k1, v1, block_tables,
                  settings: AttnSettings):
    """One decode step against the paged pool: scatter the new K/V entry,
    then attend through the block table — via the Pallas paged kernel
    (interpret-mode off-TPU; quantized pools dequant IN-kernel on the
    block-table DMA path) or the jnp gather fallback. Returns
    (o, new_cache, mass or None): `mass` [b, max_blocks] is each logical
    block's softmax share, emitted when settings.track_mass."""
    new_cache = _paged_write(cache, block_tables, k1, v1, pos1)
    b, m_blocks = block_tables.shape
    bsz = cache["pos"].shape[1]
    if settings.backend == "pallas":
        quant = paged_quant_kind(new_cache)
        scales = ((new_cache["ks"], new_cache["vs"]) if quant != "none"
                  else ())

        def decode(q1, kb, vb, pos, tables, pos1, *scales):
            ks, vs = scales or (None, None)
            return kops.paged_decode_attention(
                q1, kb, vb, pos, tables, pos1, window=blk.window,
                chunk=blk.chunk, backend="pallas", k_scales=ks, v_scales=vs,
                return_mass=settings.track_mass)

        out = _over_kv_heads(
            decode, (q[:, 0], new_cache["kb"], new_cache["vb"],
                     new_cache["pos"], block_tables, pos1) + scales,
            (1, 2, 2, None, None, None) + (2,) * len(scales),
            (1, "mean") if settings.track_mass else 1)
        if settings.track_mass:
            o, mass = out
            return o[:, None], new_cache, mass
        return out[:, None], new_cache, None
    kops.note_trace("decode", "jnp")
    virt = _paged_gather(new_cache, block_tables)
    if settings.track_mass:
        o, p = _decode_attend(q, virt, blk, pos1, return_probs=True)
        # p [b, K, G, 1, mB*bs]: average heads, fold positions into blocks
        mass = p.mean(axis=(1, 2))[:, 0].reshape(b, m_blocks, bsz).sum(-1)
        return o, new_cache, mass
    return _decode_attend(q, virt, blk, pos1), new_cache, None


# ---------------------------------------------------------------------------
# Block entry point
# ---------------------------------------------------------------------------

def attn_apply(params, cfg: ModelConfig, blk: BlockSpec, x, positions,
               cache=None, decode: bool = False, context: int = 0,
               settings: AttnSettings = AttnSettings(), block_tables=None):
    """x [b, s, d]; positions [b, s] (s=1 for decode). `block_tables`
    [b, max_blocks] routes decode through a paged pool cache (see the
    paged-KV section above) when the layer's cache is paged.

    Returns (y [b, s, d], new_cache or None, aux dict). `aux` carries
    "attn_mass" [b, max_blocks] on paged decode when settings.track_mass
    (the block-retention signal); empty otherwise.
    """
    b, s, d = x.shape
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.q_group
    h = layers.rmsnorm(params["norm"], x, cfg.norm_eps)
    wq, wk, wv, wo = (params["wq"], params["wk"], params["wv"], params["wo"])
    if settings.gather_weights:
        wq = gather_fsdp(wq, None, "q_w")
        wk = gather_fsdp(wk, None, "kv_w")
        wv = gather_fsdp(wv, None, "kv_w")
        wo = gather_fsdp(wo, "q_w", None)
    q = layers.matmul(h, wq).reshape(b, s, K, G, hd)
    k = layers.matmul(h, wk).reshape(b, s, K, hd)
    v = layers.matmul(h, wv).reshape(b, s, K, hd)
    use_repeat = settings.repeat_kv
    if use_repeat is None:                       # auto (DESIGN.md §4)
        from repro.parallel import axes as pax
        mesh = pax.current_mesh()
        msize = mesh.shape.get("model", 1) if mesh is not None else 1
        use_repeat = (G > 1 and msize > 1 and K % msize != 0
                      and (K * G) % msize == 0)
    appending = (not decode and cache is not None
                 and not isinstance(cache, str))
    use_repeat = use_repeat and G > 1 and not decode and not appending
    if not use_repeat:
        # kv-head sharding (replicates over model when K doesn't divide it)
        q = shard(q, "batch", "seq", "kv_heads", None, None)
        k = shard(k, "batch", "seq", "kv_heads", None)
        v = shard(v, "batch", "seq", "kv_heads", None)
    if blk.rope:
        q = layers.apply_rope(q.reshape(b, s, K * G, hd), positions,
                              cfg.rope_theta).reshape(b, s, K, G, hd)
        k = layers.apply_rope(k, positions, cfg.rope_theta)

    aux = {}
    if decode:
        assert cache is not None and s == 1
        pos1 = positions.reshape(b)              # accept [b] or [b, 1]
        if is_paged_cache(cache):
            assert block_tables is not None, \
                "paged cache needs block_tables at decode"
            o, new_cache, mass = _paged_decode(q, cache, blk, pos1, k[:, 0],
                                               v[:, 0], block_tables,
                                               settings)
            if mass is not None:
                aux["attn_mass"] = mass
        else:
            L = cache["pos"].shape[1]
            # inert rows (pos1 < 0) drop their ring write entirely — slot L
            # is out of range and mode="drop" discards it
            slot = jnp.where(pos1 >= 0, pos1 % L, L)
            bidx = jnp.arange(b)
            new_cache = {
                "k": cache["k"].at[bidx, slot].set(k[:, 0], mode="drop"),
                "v": cache["v"].at[bidx, slot].set(v[:, 0], mode="drop"),
                "pos": cache["pos"].at[bidx, slot].set(pos1, mode="drop"),
            }
            o = _decode_attend(q, new_cache, blk, pos1)
    elif appending:
        # chunked prefill: a real cache on the sequence path means "append
        # this chunk to what the earlier chunks already wrote"
        o, new_cache = _chunk_append(q, k, v, cache, blk, positions,
                                     block_tables, settings)
    else:
        kpos = positions
        if use_repeat:
            kr = jnp.repeat(k, G, axis=2)        # kv index h -> h // G
            vr = jnp.repeat(v, G, axis=2)
            qh = q.reshape(b, s, K * G, 1, hd)
            qh = shard(qh, "batch", "seq", "heads", None, None)
            kr = shard(kr, "batch", "seq", "heads", None)
            vr = shard(vr, "batch", "seq", "heads", None)
            o = _seq_attention(qh, kr, vr, positions, kpos, blk, settings)
            o = o.reshape(b, s, K, G, hd)
        else:
            o = _seq_attention(q, k, v, positions, kpos, blk, settings)
        new_cache = (_cache_from_prefill(k, v, positions, blk, context)
                     if cache == "build" else None)

    o = o.reshape(b, s, cfg.n_heads * hd)
    y = layers.matmul(o, wo)
    return shard(y, "batch", "seq", "embed"), new_cache, aux
