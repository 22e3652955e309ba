"""Serving steps: prefill (builds the ring KV / recurrent caches, returns
last-token logits), decode (one token per sequence against the cache), and
the slot-pool operations the serving engine's continuous batching uses
(claim a slot by overwriting it with a fresh prefill; batched decode over
heterogeneous per-slot positions rides the ring cache's slot = pos % L
layout unchanged).

Paged-pool notes (see serving.engine for the admission/eviction policy):

- Block tables are the ONLY routing state. A physical block written once
  (by whole-prompt or chunked prefill) can be mapped by many tables at
  once — refcounted prefix sharing needs no extra step: sharers simply
  seed the leading entries of their table with the shared prefix's
  physical ids and prefill only their suffix (absolute positions, so the
  KV written is identical to an unshared prefill of the full prompt).
  Copy-on-write at the prefix boundary is BY RECOMPUTE: the sharer never
  mutates a shared block; its boundary tokens are re-prefilled into a
  private block it allocated itself.
- Decode ticks run at full lane width; rows whose lane is empty or still
  mid-chunk-prefill are INERT (position -1, empty table) and their writes
  land in scratch block 0 / are dropped (see attention._paged_write), so
  a decode tick can never clobber KV a concurrent chunked prefill wrote.
- Eviction frees physical blocks but writes nothing: reset_pool_blocks
  invalidates re-linked blocks (pos -1) before a NEW owner's table routes
  a read through them, and an evicted request re-prefills prompt+emitted
  from scratch on readmission — no KV survives eviction."""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention
from repro.models import model as M


def make_prefill_step(cfg: ModelConfig,
                      settings: Optional[M.ModelSettings] = None):
    settings = settings or M.serving_settings()
    psettings = dataclasses.replace(settings, build_cache=True)

    def prefill_step(params, tokens, context: int, prefix_embeds=None):
        logits, cache, _ = M.apply(params, cfg, tokens,
                                   prefix_embeds=prefix_embeds,
                                   settings=psettings, context=context,
                                   logits_last_only=True)
        return logits[:, -1], cache

    return prefill_step


def make_decode_step(cfg: ModelConfig,
                     settings: Optional[M.ModelSettings] = None):
    settings = settings or M.serving_settings()

    def decode_step(params, tokens, positions, cache, context: int):
        logits, new_cache, _ = M.apply(params, cfg, tokens,
                                       positions=positions, cache=cache,
                                       decode=True, settings=settings,
                                       context=context)
        return logits[:, -1], new_cache

    return decode_step


# ---------------------------------------------------------------------------
# Slot pool: a batch of independent ring caches the engine claims/frees
# ---------------------------------------------------------------------------

def init_slot_pool(cfg: ModelConfig, n_slots: int, context: int):
    """The engine's KV/recurrent slot pool: one cache tree whose batch dim
    is the slot index. Freshly initialized slots hold pos=-1 everywhere
    (every ring entry masked)."""
    return M.init_cache(cfg, n_slots, context)


def write_cache_slots(cfg: ModelConfig, pool, one, slots):
    """Batched write_cache_slot: scatter a width-W prefill cache (batch=W)
    into pool slots `slots` [W] in one step. Padding rows carry slot index
    >= pool width and are DROPPED by the scatter (mode='drop'), which is
    what lets the engine-level batched prefill pad every admission group to
    the pool width and keep ONE compile per prompt bucket."""
    def upd(axis):
        def f(P, o):
            idx = (slice(None),) * axis + (slots,)
            return P.at[idx].set(o.astype(P.dtype), mode="drop")
        return f

    return {
        "units": [jax.tree.map(upd(1), pool["units"][i], one["units"][i])
                  for i in range(len(cfg.unit))],
        "tail": [jax.tree.map(upd(0), pool["tail"][i], one["tail"][i])
                 for i in range(len(cfg.tail))],
    }


def write_cache_slot(cfg: ModelConfig, pool, one, slot):
    """Overwrite slot `slot` of a pool cache with a single-sequence cache
    (batch=1). Unit caches are stacked over repeats (batch is axis 1); tail
    caches lead with batch (axis 0). Prefill rings always span the full
    cache_len (attention._cache_from_prefill pads short prompts), so this
    is a whole-slot overwrite: whatever a freed slot accumulated while
    riding along in batched decode is wiped on claim."""
    def upd(axis):
        return lambda P, o: jax.lax.dynamic_update_slice_in_dim(
            P, o.astype(P.dtype), slot, axis=axis)

    return {
        "units": [jax.tree.map(upd(1), pool["units"][i], one["units"][i])
                  for i in range(len(cfg.unit))],
        "tail": [jax.tree.map(upd(0), pool["tail"][i], one["tail"][i])
                 for i in range(len(cfg.tail))],
    }


def make_slot_prefill_step(cfg: ModelConfig,
                           settings: Optional[M.ModelSettings] = None):
    """Prefill ONE sequence (tokens [1, p]) directly into slot `slot` of a
    donated pool cache. Returns (last-token logits [1, V], new pool). One
    compile per distinct prompt length (bucketed traces keep that small);
    the decode step stays a single compile at pool width."""
    settings = settings or M.serving_settings()
    psettings = dataclasses.replace(settings, build_cache=True)

    def prefill_into_slot(params, tokens, slot, pool, context: int):
        logits, one, _ = M.apply(params, cfg, tokens, settings=psettings,
                                 context=context, logits_last_only=True)
        return logits[:, -1], write_cache_slot(cfg, pool, one, slot)

    return prefill_into_slot


def make_batch_prefill_step(cfg: ModelConfig,
                            settings: Optional[M.ModelSettings] = None):
    """Engine-level batched prefill: prefill tokens [W, p] (W = pool width,
    padding rows filled with dummy prompts) and scatter each row into pool
    slot `slots[w]` (index >= W drops the row). One compile per prompt
    bucket p, shared by every admission tick that hits the bucket."""
    settings = settings or M.serving_settings()
    psettings = dataclasses.replace(settings, build_cache=True)

    def prefill_into_slots(params, tokens, slots, pool, context: int):
        logits, one, _ = M.apply(params, cfg, tokens, settings=psettings,
                                 context=context, logits_last_only=True)
        return logits[:, -1], write_cache_slots(cfg, pool, one, slots)

    return prefill_into_slots


# ---------------------------------------------------------------------------
# Paged KV pool: fixed-size position blocks + per-sequence block tables
# ---------------------------------------------------------------------------
#
# Full-context attention layers store KV in a POOL of `block`-position
# blocks ({"kb": [n_blocks, block, K, hd], "vb": ..., "pos": [n_blocks,
# block]}; models.attention.is_paged_cache) indexed through per-sequence
# block tables, so a short request holds ceil(written / block) blocks
# instead of a whole max-context ring. Everything else — recurrent states,
# short windowed/chunked rings — stays a per-lane slot exactly like the
# ring pool. Physical block 0 is the scratch block (inactive decode lanes
# and padded prefill rows read/write it harmlessly); the serving engine's
# BlockAllocator therefore hands out ids 1..n_blocks-1.


def is_paged_block(blk, context: int) -> bool:
    """Which layers page: attention whose ring spans the full context (the
    dominant KV cost). Short windowed/chunked rings stay per-lane."""
    return blk.is_attn and blk.cache_len(context) == context


def init_paged_pool(cfg: ModelConfig, n_lanes: int, n_blocks: int,
                    block: int, context: int, abstract: bool = False,
                    kv_quant: str = "none", mesh=None):
    """The paged serving pool: paged layers get block-pool leaves (shared
    across lanes), everything else a per-lane cache like init_slot_pool.
    `context` must be a multiple of `block` (the executor rounds up).

    kv_quant != "none" stores kb/vb quantized (int8, or int4 packed two
    nibbles per uint8 byte halving the last dim) with per-(position, kv
    head) f32 absmax scales in sibling "ks"/"vs" leaves. The pool is
    self-describing: read/write paths pick the codec off the leaf dtypes
    (attention.paged_quant_kind), so a quantized pool can never be
    misread as fp.

    With a `mesh`, the pool is materialized straight into its shardings
    (pool_shardings): paged leaves split KV heads over the mesh."""
    if context % block:
        raise ValueError(f"paged pool context {context} must be a multiple "
                         f"of the kv block size {block}")
    if kv_quant not in ("none", "int8", "int4"):
        raise ValueError(f"unknown kv_quant {kv_quant!r}")
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    if kv_quant == "int4" and hd % 2:
        raise ValueError(f"int4 KV packs nibble pairs; head_dim {hd} "
                         "must be even")

    def paged_leaf():
        if kv_quant == "none":
            return {
                "kb": jax.ShapeDtypeStruct((n_blocks, block, K, hd),
                                           jnp.bfloat16),
                "vb": jax.ShapeDtypeStruct((n_blocks, block, K, hd),
                                           jnp.bfloat16),
                "pos": jax.ShapeDtypeStruct((n_blocks, block), jnp.int32),
            }
        qdt = jnp.int8 if kv_quant == "int8" else jnp.uint8
        qhd = hd if kv_quant == "int8" else hd // 2
        return {
            "kb": jax.ShapeDtypeStruct((n_blocks, block, K, qhd), qdt),
            "vb": jax.ShapeDtypeStruct((n_blocks, block, K, qhd), qdt),
            "ks": jax.ShapeDtypeStruct((n_blocks, block, K), jnp.float32),
            "vs": jax.ShapeDtypeStruct((n_blocks, block, K), jnp.float32),
            "pos": jax.ShapeDtypeStruct((n_blocks, block), jnp.int32),
        }

    def one_cache(blk):
        if is_paged_block(blk, context):
            return paged_leaf()
        return M.block_cache_init(cfg, blk, n_lanes, context, abstract=True)

    def _materialize(s):
        if s.dtype == jnp.int32:   # position buffers start invalid
            return jnp.full(s.shape, -1, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    def stacked(blk):
        one = one_cache(blk)
        stack = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.repeats,) + s.shape, s.dtype),
            one)
        return stack if abstract else jax.tree.map(_materialize, stack)

    if mesh is not None and not abstract:
        shapes = init_paged_pool(cfg, n_lanes, n_blocks, block, context,
                                 abstract=True, kv_quant=kv_quant)
        return jax.jit(lambda: jax.tree.map(_materialize, shapes),
                       out_shardings=pool_shardings(shapes, mesh))()
    pool = {"units": [stacked(blk) for blk in cfg.unit], "tail": []}
    for blk in cfg.tail:
        one = one_cache(blk)
        pool["tail"].append(one if abstract
                            else jax.tree.map(_materialize, one))
    return pool


# logical axes of one layer's paged pool leaves (layer-stacked leaves lead
# with "layers")
_PAGED_AXES = {"kb": (None, None, "kv_heads", None),
               "vb": (None, None, "kv_heads", None),
               "ks": (None, None, "kv_heads"),
               "vs": (None, None, "kv_heads"),
               "pos": (None, None)}


def pool_shardings(pool, mesh):
    """NamedShardings of a paged serving pool on `mesh` under the ambient
    axis rules: block-pool leaves split their KV-head dim (the attention
    kernels run per head shard), per-lane leaves are replicated."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.parallel import axes as pax

    def place(P, stacked):
        lead = ("layers",) if stacked else ()
        if not _is_paged_leaf(P):
            return jax.tree.map(
                lambda v: NamedSharding(mesh, PartitionSpec()), P)
        return {k: NamedSharding(mesh, pax.logical_to_spec(
            lead + _PAGED_AXES[k], mesh=mesh, shape=v.shape))
            for k, v in P.items()}

    return {"units": [place(P, True) for P in pool["units"]],
            "tail": [place(P, False) for P in pool["tail"]]}


def write_paged_prefill(cfg: ModelConfig, pool, one, lanes, tables,
                        block: int):
    """Scatter a width-W prefill cache into the paged pool: paged layers
    split each row's full-context ring (identity layout: prefill positions
    start at 0, so slot i <-> position i) into `context // block` logical
    blocks and scatter them to the physical ids in `tables` [W, mB]
    (entries -1 — unallocated logical blocks, i.e. ring padding beyond the
    prompt, and whole padding rows — land in scratch block 0); per-lane
    layers scatter to `lanes` [W] with pool-width padding dropped."""
    def lane_upd(axis):
        def f(P, o):
            idx = (slice(None),) * axis + (lanes,)
            return P.at[idx].set(o.astype(P.dtype), mode="drop")
        return f

    def paged_upd(P, o, batch_axis):
        # o k/v: [..., W, L, K, hd] with L = mB * block; pos: [..., W, L].
        # Quantized pools quantize the prefill ring here (the exact same
        # per-row codec _paged_write applies on decode appends).
        kind = attention.paged_quant_kind(P)
        W, mB = tables.shape
        flat = jnp.where(tables >= 0, tables, 0).reshape(-1)      # [W*mB]
        idx = (slice(None),) * batch_axis + (flat,)

        def blocked(o_l):
            shp = o_l.shape[:batch_axis] + (W * mB, block) \
                + o_l.shape[batch_axis + 2:]
            return o_l.reshape(shp)

        new = dict(P)
        for kk, pk, sk in (("k", "kb", "ks"), ("v", "vb", "vs")):
            q, s = attention.quantize_kv(o[kk], kind)
            new[pk] = P[pk].at[idx].set(blocked(q).astype(P[pk].dtype))
            if s is not None:
                new[sk] = P[sk].at[idx].set(blocked(s))
        new["pos"] = P["pos"].at[idx].set(
            blocked(o["pos"]).astype(P["pos"].dtype))
        return new

    units = []
    for i, blk in enumerate(cfg.unit):
        P, o = pool["units"][i], one["units"][i]
        if isinstance(P, dict) and "kb" in P:
            units.append(paged_upd(P, o, batch_axis=1))
        else:
            units.append(jax.tree.map(lane_upd(1), P, o))
    tail = []
    for i, blk in enumerate(cfg.tail):
        P, o = pool["tail"][i], one["tail"][i]
        if isinstance(P, dict) and "kb" in P:
            tail.append(paged_upd(P, o, batch_axis=0))
        else:
            tail.append(jax.tree.map(lane_upd(0), P, o))
    return {"units": units, "tail": tail}


def make_paged_prefill_step(cfg: ModelConfig,
                            settings: Optional[M.ModelSettings] = None):
    """Batched prefill into the paged pool: tokens [W, p], lanes [W],
    tables [W, context // block]. One compile per prompt bucket."""
    settings = settings or M.serving_settings()
    psettings = dataclasses.replace(settings, build_cache=True)

    def prefill_paged(params, tokens, lanes, tables, pool, context: int):
        logits, one, _ = M.apply(params, cfg, tokens, settings=psettings,
                                 context=context, logits_last_only=True)
        block = pool_block_size(pool, default=1)
        return logits[:, -1], write_paged_prefill(cfg, pool, one, lanes,
                                                  tables, block)

    return prefill_paged


def make_paged_decode_step(cfg: ModelConfig,
                           settings: Optional[M.ModelSettings] = None):
    """One batched decode tick through the block tables: a single compile
    at lane width regardless of pool occupancy."""
    settings = settings or M.serving_settings()

    def decode_paged(params, tokens, positions, tables, pool, context: int):
        logits, new_pool, aux = M.apply(params, cfg, tokens,
                                        positions=positions, cache=pool,
                                        decode=True, settings=settings,
                                        context=context, block_tables=tables)
        # mass [b, max_blocks] (layer-summed per-block attention mass) when
        # settings.attn.track_mass, else None — the retention policy's feed
        return logits[:, -1], new_pool, aux.get("attn_mass")

    return decode_paged


def _is_paged_leaf(P) -> bool:
    return isinstance(P, dict) and "kb" in P


def gather_pool_lanes(pool, lane_ids):
    """Sub-pool view of a paged pool at lanes `lane_ids` [w]: per-lane
    leaves (recurrent states, short windowed rings) are gathered down to
    width w, paged block-pool leaves pass through whole (block tables route
    them, so they need no lane axis). Padding ids >= n_lanes clamp to a
    real lane on the read — harmless, because scatter_pool_lanes drops
    their write-back."""
    def take(axis):
        def f(P):
            idx = ((slice(None),) * axis
                   + (jnp.clip(lane_ids, 0, P.shape[axis] - 1),))
            return P[idx]
        return f

    return {
        "units": [P if _is_paged_leaf(P) else jax.tree.map(take(1), P)
                  for P in pool["units"]],
        "tail": [P if _is_paged_leaf(P) else jax.tree.map(take(0), P)
                 for P in pool["tail"]],
    }


def scatter_pool_lanes(pool, sub, lane_ids):
    """Write a width-w sub-pool (gather_pool_lanes layout) back into the
    full pool: per-lane rows land at `lane_ids` (ids >= n_lanes are padding
    and DROPPED), updated paged leaves replace the pool's wholesale."""
    def put(axis):
        def f(P, o):
            idx = (slice(None),) * axis + (lane_ids,)
            return P.at[idx].set(o.astype(P.dtype), mode="drop")
        return f

    return {
        "units": [o if _is_paged_leaf(P) else jax.tree.map(put(1), P, o)
                  for P, o in zip(pool["units"], sub["units"])],
        "tail": [o if _is_paged_leaf(P) else jax.tree.map(put(0), P, o)
                 for P, o in zip(pool["tail"], sub["tail"])],
    }


def make_compact_decode_step(cfg: ModelConfig,
                             settings: Optional[M.ModelSettings] = None):
    """Paged decode at a COMPACTED width w <= n_lanes: gather the w active
    lanes' per-lane caches, run one batched decode at width w through their
    (trimmed) block tables, scatter the updates back. jax.jit specializes
    per (w, table-width) bucket, so each touched bucket costs one compile
    and a tick with 3 active lanes stops paying for the padded remainder
    of the pool."""
    settings = settings or M.serving_settings()

    def decode_compact(params, tokens, positions, tables, lane_ids, pool,
                       context: int):
        sub = gather_pool_lanes(pool, lane_ids)
        logits, new_sub, aux = M.apply(params, cfg, tokens,
                                       positions=positions, cache=sub,
                                       decode=True, settings=settings,
                                       context=context, block_tables=tables)
        return (logits[:, -1], scatter_pool_lanes(pool, new_sub, lane_ids),
                aux.get("attn_mass"))

    return decode_compact


def make_chunk_prefill_step(cfg: ModelConfig,
                            settings: Optional[M.ModelSettings] = None):
    """Chunked prefill: run tokens [w, C] at absolute positions [w, C]
    (-1 = padding) against the LIVE pool — attention layers append the
    chunk to what earlier chunks wrote (paged layers through `tables`,
    per-lane rings in place) and attend over history + chunk, which is
    exactly that slice of a whole-prompt prefill. Returns each row's
    last-valid-position logits (meaningful for rows whose chunk completes
    the prompt) and the updated pool. One compile per (width bucket,
    table width); C is fixed by the engine's chunk size."""
    settings = settings or M.serving_settings()
    psettings = dataclasses.replace(settings, build_cache=True)

    def prefill_chunk(params, tokens, positions, tables, lane_ids, pool,
                      context: int):
        sub = gather_pool_lanes(pool, lane_ids)
        logits, new_sub, _ = M.apply(params, cfg, tokens,
                                     positions=positions, cache=sub,
                                     decode=False, settings=psettings,
                                     context=context, block_tables=tables)
        lens = jnp.sum(positions >= 0, axis=1)
        idx = jnp.maximum(lens - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return last, scatter_pool_lanes(pool, new_sub, lane_ids)

    return prefill_chunk


def pool_block_size(pool, default: int = 0) -> int:
    """The kv block size a paged pool was built with (from any paged leaf).
    `default` covers pools with nothing to page (all-recurrent or
    short-window archs, where paged mode degenerates to per-lane slots)."""
    for P in list(pool["units"]) + list(pool["tail"]):
        if isinstance(P, dict) and "kb" in P:
            return int(P["pos"].shape[-1])
    return default


def reset_pool_blocks(pool, ids):
    """Invalidate physical blocks `ids` [W] (pos = -1) before a freed block
    is re-linked into a new sequence's table mid-decode — without it the
    block's stale positions from its previous owner would pass the decode
    mask. Padding entries may point at scratch block 0 (reset is harmless
    there)."""
    def upd(P, lead):
        idx = (slice(None),) * lead + (ids,)
        return {**P, "pos": P["pos"].at[idx].set(-1)}

    return {
        "units": [upd(P, 1) if isinstance(P, dict) and "kb" in P else P
                  for P in pool["units"]],
        "tail": [upd(P, 0) if isinstance(P, dict) and "kb" in P else P
                 for P in pool["tail"]],
    }


def clear_pool(pool):
    """Re-initialize a live pool wholesale: every int32 validity buffer
    (paged block `pos`, ring positions) back to -1, every payload leaf to
    zeros — `init_paged_pool`'s freshly-materialized state without
    rebuilding the tree. This is the executor-REUSE hook for engine
    snapshot/restore: `Engine.resume` re-materializes all KV via
    re-prefill anyway, so a preempted replica hands its existing device
    buffers to the restored engine instead of paying a fresh allocation."""
    def f(x):
        # fresh buffers on the same devices and shardings as the old ones
        if hasattr(x, "dtype") and x.dtype == jnp.int32:
            return jnp.full_like(x, -1, device=x.sharding)
        return jnp.zeros_like(x, device=x.sharding)

    return jax.tree.map(f, pool)


def _sharding_ctx_key():
    """The ambient sharding context shard()/gather_fsdp bake into a trace
    (parallel.axes thread-locals). jax.jit's own cache does not key on it,
    so the memoized steps below must — otherwise a run under different
    axis_rules/mesh would reuse a trace with the wrong constraints."""
    from repro.parallel import axes as pax
    mesh = pax.current_mesh()
    return (mesh, tuple(sorted(pax.current_rules().items())))


@functools.lru_cache(maxsize=64)
def _jitted_serve_steps(cfg, settings, mode: str, ctx_key):
    if mode == "plain":
        prefill = jax.jit(make_prefill_step(cfg, settings),
                          static_argnames=("context",))
        decode = jax.jit(make_decode_step(cfg, settings),
                         static_argnames=("context",), donate_argnums=(3,))
        return prefill, decode
    if mode == "slot":
        prefill = jax.jit(make_slot_prefill_step(cfg, settings),
                          static_argnames=("context",), donate_argnums=(3,))
        batch = jax.jit(make_batch_prefill_step(cfg, settings),
                        static_argnames=("context",), donate_argnums=(3,))
        decode = jax.jit(make_decode_step(cfg, settings),
                         static_argnames=("context",), donate_argnums=(3,))
        return prefill, batch, decode
    if mode == "paged":
        prefill = jax.jit(make_paged_prefill_step(cfg, settings),
                          static_argnames=("context",), donate_argnums=(4,))
        decode = jax.jit(make_paged_decode_step(cfg, settings),
                         static_argnames=("context",), donate_argnums=(4,))
        reset = jax.jit(reset_pool_blocks, donate_argnums=(0,))
        compact = jax.jit(make_compact_decode_step(cfg, settings),
                          static_argnames=("context",), donate_argnums=(5,))
        chunk = jax.jit(make_chunk_prefill_step(cfg, settings),
                        static_argnames=("context",), donate_argnums=(5,))
        return prefill, decode, reset, compact, chunk
    raise ValueError(mode)


def serve_steps(cfg: ModelConfig,
                settings: Optional[M.ModelSettings] = None):
    """Jitted (prefill, decode) pair, memoized per (cfg, settings, ambient
    sharding context): repeated greedy_generate calls (tests, examples)
    reuse the compiled steps instead of re-tracing per call. `context` is
    static and the decode cache is donated in place."""
    return _jitted_serve_steps(cfg, settings or M.serving_settings(),
                               "plain", _sharding_ctx_key())


def slot_serve_steps(cfg: ModelConfig,
                     settings: Optional[M.ModelSettings] = None):
    """Jitted (prefill-into-slot, batched-prefill-into-slots, decode)
    triple for the engine's slot pool, memoized like serve_steps so
    successive executors (e.g. the serve driver's --policy both runs)
    share compiled steps instead of paying the whole compile set again.
    Pool arguments are donated."""
    return _jitted_serve_steps(cfg, settings or M.serving_settings(),
                               "slot", _sharding_ctx_key())


def paged_serve_steps(cfg: ModelConfig,
                      settings: Optional[M.ModelSettings] = None):
    """Jitted (batched-prefill, decode, reset-blocks, compact-decode,
    chunk-prefill) tuple for the paged block pool, memoized like
    slot_serve_steps. The full-width decode is one compile at lane width;
    the compact decode specializes per touched (lane, table) width bucket;
    prefill compiles once per prompt bucket (padded to lane width) and
    chunk-prefill once per touched width bucket at the fixed chunk
    length."""
    return _jitted_serve_steps(cfg, settings or M.serving_settings(),
                               "paged", _sharding_ctx_key())


def greedy_generate(params, cfg: ModelConfig, prompt_tokens, n_steps: int,
                    context: int, settings: Optional[M.ModelSettings] = None):
    """Greedy decoding with jitted, cache-donating steps (serve_steps):
    the engine's per-request reference path."""
    b, p = prompt_tokens.shape
    prefill, decode = serve_steps(cfg, settings)
    last_logits, cache = prefill(params, prompt_tokens, context=context)
    out = []
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    for t in range(n_steps):
        out.append(tok)
        pos = jnp.full((b,), p + t, jnp.int32)
        logits, cache = decode(params, tok[:, None], pos, cache,
                               context=context)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.stack(out, axis=1)
