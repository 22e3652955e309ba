"""The jax executors behind the serving engine: a slotted ring-cache pool
(baseline) and a paged KV block pool, each with jitted prefill / batched-
decode steps.

One decode compile serves the whole run (the pool width and context are
fixed); prefill compiles once per distinct prompt length — synthetic
traces draw prompts from small bucket sets, so the compile count stays
bounded and every compile serves traffic (zero throwaway compiles when
planning went through the simulator). Engine-level batched prefill pads
each same-tick, same-bucket admission group to the pool width, so a burst
of admissions costs ONE prefill call instead of one per request.

The paged executor additionally supports lane COMPACTION (decode at the
smallest bucketed width covering the active lanes, one compile per
touched bucket instead of full-width padding every tick) and CHUNKED
prefill (append long prompts to the live pool kv_block-aligned chunks at
a time so one long prompt stops holding the tick hostage).

The chunked step is STATELESS per call — each chunk carries its own
absolute `starts` and block tables, so the engine reuses it unchanged for
the overload machinery: a prefix SHARER prefills only its private suffix
(starts at `shared_blocks * kv_block`, reading the shared prefix KV
through its seeded table — the boundary partial block is copy-on-write by
recompute into an owned block), and an EVICTED request re-prefills
`prompt + already-emitted tokens` from scratch into freshly allocated
blocks. No executor state survives an eviction; everything is the block
tables.

The paged executor marks its host work with fixed-name spans in the
profiler's trace (`jax.profiler.TraceAnnotation`, on the same timeline as
the device's programs): `executor.decode` / `.chunk` / `.prefill` /
`.reset` cover one call, and inside decode, chunk and prefill
`executor.<kind>.pack` builds the numpy inputs, `.launch` copies them to
the device and enqueues the jitted step, and `.pull` reads the next tokens
back (waiting for the device). With no profiler session they cost under a
microsecond each.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.parallel import axes as pax
from repro.runtime import serve_step as SS


def _compile_count(fn) -> int:
    """Compiled variants a jitted step holds (one per input signature)."""
    return int(fn._cache_size())


def _pad_token(cfg: ModelConfig) -> int:
    """Dummy token id for padding rows — must be a REAL vocab entry (tiny
    test configs can have vocab_size <= 2, where a hardcoded id would
    index past the embedding table)."""
    pad = min(2, cfg.vocab_size - 1)
    assert 0 <= pad < cfg.vocab_size, cfg.vocab_size
    return pad


def _pad_batch(width: int, slots: Sequence[int],
               prompts: Sequence[Sequence[int]], pad_token: int):
    """Pack a same-length admission group into pool-width numpy arrays:
    padding rows carry dummy prompts (`pad_token`) and index `width` — out
    of bounds, so the prefill scatter drops them (mode='drop')."""
    p = len(prompts[0])
    toks = np.full((width, p), pad_token, np.int32)
    idx = np.full((width,), width, np.int32)
    for i, (s, pr) in enumerate(zip(slots, prompts)):
        toks[i] = pr
        idx[i] = s
    return toks, idx


def _pow2_buckets(n: int) -> tuple:
    """Power-of-two widths up to n, always including n itself."""
    out = []
    w = 1
    while w < n:
        out.append(w)
        w *= 2
    out.append(int(n))
    return tuple(out)


def _cover(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (buckets sorted ascending; n <= max)."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


class JaxExecutor:
    """Executes engine slot operations against a real parameter set.

    The pool cache (batch dim = slot index) lives here and is donated
    through every step: prefill overwrites one slot in place, decode
    advances all slots in one batched heterogeneous-position step (the
    ring cache's slot = pos % L layout needs no per-sequence alignment).
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int,
                 context: int, settings: Optional[M.ModelSettings] = None):
        self.params = params
        self.cfg = cfg
        self.settings = settings
        self.n_slots = int(n_slots)
        self.context = int(context)
        self.pool = SS.init_slot_pool(cfg, self.n_slots, self.context)

    def _steps(self):
        # fetched per call: memoized on (cfg, settings, ambient sharding
        # context), so a second executor for the same model (--policy both)
        # reuses the compiled steps while a different mesh/rules retraces
        return SS.slot_serve_steps(self.cfg, self.settings)

    def prefill(self, slot: int, prompt: Sequence[int]) -> int:
        prefill_step, _, _ = self._steps()
        tokens = jnp.asarray(list(prompt), jnp.int32)[None, :]
        logits, self.pool = prefill_step(self.params, tokens, slot,
                                         self.pool, context=self.context)
        return int(jnp.argmax(logits[0], axis=-1))

    def prefill_batch(self, slots: Sequence[int],
                      prompts: Sequence[Sequence[int]],
                      tables=None) -> List[int]:
        """One padded prefill for a same-bucket admission group: tokens are
        padded to the pool width W (dummy rows use the config's pad token)
        and the scatter drops rows whose slot index is W (out of bounds)."""
        _, batch_step, _ = self._steps()
        toks, slot_arr = _pad_batch(self.n_slots, slots, prompts,
                                    _pad_token(self.cfg))
        logits, self.pool = batch_step(self.params, jnp.asarray(toks),
                                       jnp.asarray(slot_arr), self.pool,
                                       context=self.context)
        out = np.asarray(jnp.argmax(logits, axis=-1))
        return [int(out[i]) for i in range(len(slots))]

    def decode_width(self, n_active: int) -> int:
        """The batch width a decode tick with `n_active` lanes computes at
        (the ring pool always runs full width)."""
        return self.n_slots

    def decode(self, tokens: Sequence[int], positions: Sequence[int],
               tables=None, lanes=None) -> List[int]:
        _, _, decode_step = self._steps()
        t = jnp.asarray(list(tokens), jnp.int32)[:, None]
        p = jnp.asarray(list(positions), jnp.int32)
        logits, self.pool = decode_step(self.params, t, p, self.pool,
                                        context=self.context)
        return np.asarray(jnp.argmax(logits, axis=-1)).astype(int).tolist()

    def compile_counts(self) -> dict:
        """Compiled-variant counts of the serving steps (prefill: one per
        prompt-length bucket; decode: one) — the driver reports them so
        'every compile served traffic' is checkable."""
        single, batch, decode_step = self._steps()
        return {"prefill": _compile_count(batch) + _compile_count(single),
                "decode": _compile_count(decode_step)}


class PagedJaxExecutor:
    """Engine lane operations over the paged KV block pool.

    Full-context attention layers store KV in `n_blocks` shared blocks of
    `kv_block` positions (physical id 0 is the scratch block for inactive
    lanes, so the pool is allocated one block larger); each active lane's
    logical layout reaches the pool through its block table. Prefill
    scatters whole blocks, padded to lane width per prompt bucket like the
    ring executor.

    Decode runs either full width (one compile at lane width regardless of
    pool occupancy) or, with `compact=True`, at the smallest bucketed
    width covering the active lanes: active lanes are packed to the front,
    their tables trimmed to the bucketed maximum of blocks actually
    allocated, and the per-lane caches gathered/scattered around the step
    — so a tick with 3 active sequences stops paying for 24 padded lanes,
    at the cost of one compile per touched (lane, table) width bucket.

    `chunk > 0` enables chunked prefill (`prefill_chunks`): prompts are
    appended to the live pool `chunk` positions at a time, interleaved
    with decode ticks by the engine. Exactness relies on every mixer
    resuming from carried state, which holds for attention (the cache IS
    the state) but not for mLSTM's fresh-scan sequence path — hence the
    all-attention gate.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_lanes: int,
                 n_blocks: int, kv_block: int, context: int,
                 settings: Optional[M.ModelSettings] = None,
                 compact: bool = False,
                 lane_buckets: Optional[Sequence[int]] = None,
                 chunk: int = 0, kv_quant: str = "none",
                 kv_retain: int = 0, track_mass: bool = False):
        if kv_block < 1:
            raise ValueError(f"kv_block must be >= 1, got {kv_block}")
        if kv_retain < 0:
            raise ValueError(f"kv_retain must be >= 0, got {kv_retain}")
        self.params = params
        self.cfg = cfg
        self.kv_quant = str(kv_quant)
        self.kv_retain = int(kv_retain)
        if self.kv_retain or track_mass:
            # retention ranks blocks by attention mass — decode steps must
            # account it, so bake track_mass into the jitted settings.
            # `track_mass=True` alone pays the accounting without a
            # standing retention cap, for engines whose degradation
            # ladder may engage `bend_retain` mid-run.
            base = settings or M.serving_settings()
            settings = dataclasses.replace(
                base, attn=dataclasses.replace(base.attn, track_mass=True))
        self.settings = settings
        self.n_lanes = int(n_lanes)
        self.kv_block = int(kv_block)
        # block-align the ring extent so logical blocks tile it exactly
        self.context = -(-int(context) // kv_block) * kv_block
        self.max_blocks = self.context // kv_block
        self.n_blocks = int(n_blocks)
        self.compact = bool(compact)
        if lane_buckets is None:
            self.lane_buckets = _pow2_buckets(self.n_lanes)
        else:
            bk = sorted({int(b) for b in lane_buckets})
            if not bk or bk[0] < 1:
                raise ValueError(f"bad lane_buckets {lane_buckets}")
            if bk[-1] < self.n_lanes:
                bk.append(self.n_lanes)   # must be able to cover every tick
            self.lane_buckets = tuple(bk)
        self.table_buckets = _pow2_buckets(self.max_blocks)
        self.chunk = int(chunk)
        if self.chunk and self.chunk % self.kv_block:
            raise ValueError(f"chunk={self.chunk} must be a multiple "
                             f"of kv_block={self.kv_block}")
        # recurrent mixers carry their scan state across chunks through
        # the per-lane pool leaves (mlstm_scan initial=, rglru h0, slstm
        # core), so chunked prefill works for any block tree; the engine
        # still refuses prefix_share here — shared prefix blocks hold
        # attention KV only, not the recurrent state at the boundary
        self.has_recurrent = any(not b.is_attn for b in cfg.blocks())
        # under a multi-device mesh the pool is placed straight into its
        # KV-head shardings (the jitted steps then keep it there)
        mesh = pax.current_mesh()
        self.pool = SS.init_paged_pool(
            cfg, self.n_lanes, self.n_blocks + 1, kv_block, self.context,
            kv_quant=self.kv_quant,
            mesh=mesh if mesh is not None and mesh.size > 1 else None)
        self.chunk_calls = 0
        # lane -> per-logical-block attention mass from the LAST decode
        # tick (only populated when kv_retain or track_mass enables the
        # accounting)
        self._last_mass: Dict[int, np.ndarray] = {}

    def _steps(self):
        return SS.paged_serve_steps(self.cfg, self.settings)

    def _table_array(self, tables: Sequence[Sequence[int]], rows: int,
                     width: Optional[int] = None) -> np.ndarray:
        width = self.max_blocks if width is None else width
        out = np.full((rows, width), -1, np.int32)
        for i, tbl in enumerate(tables):
            if len(tbl) > width:
                raise ValueError(f"lane {i}: table of {len(tbl)} blocks "
                                 f"exceeds table width {width}")
            out[i, :len(tbl)] = tbl
        return out

    @partial(annotate_function, name="executor.prefill")
    def prefill_batch(self, lanes: Sequence[int],
                      prompts: Sequence[Sequence[int]],
                      tables: Sequence[Sequence[int]]) -> List[int]:
        prefill_step = self._steps()[0]
        w = self.n_lanes
        with TraceAnnotation("executor.prefill.pack"):
            toks, lane_arr = _pad_batch(w, lanes, prompts,
                                        _pad_token(self.cfg))
            tbl = self._table_array(
                list(tables) + [[]] * (w - len(tables)), w)
        with TraceAnnotation("executor.prefill.launch"):
            logits, self.pool = prefill_step(
                self.params, jnp.asarray(toks), jnp.asarray(lane_arr),
                jnp.asarray(tbl), self.pool, context=self.context)
        with TraceAnnotation("executor.prefill.pull"):
            out = np.asarray(jnp.argmax(logits, axis=-1))
            return [int(out[i]) for i in range(len(lanes))]

    @partial(annotate_function, name="executor.reset")
    def fresh_blocks(self, ids: Sequence[int]) -> None:
        """Invalidate re-linked physical blocks (pos = -1) before decode
        reads them through a new owner's table. Padded to a multiple of
        the lane count (scratch block), so the common <= 1 block/lane/tick
        case stays a single compile and chunked prefill's multi-block
        ticks cost at most one more."""
        reset_step = self._steps()[2]
        w = self.n_lanes * max(1, -(-len(ids) // self.n_lanes))
        arr = np.zeros((w,), np.int32)                  # pad -> scratch
        arr[:len(ids)] = list(ids)
        self.pool = reset_step(self.pool, jnp.asarray(arr))

    def reset(self) -> None:
        """Return this executor to as-fresh state WITHOUT rebuilding its
        device buffers: the whole pool's validity metadata is invalidated
        (`serve_step.clear_pool`) and the per-lane chunk/mass bookkeeping
        dropped. `Engine.resume` re-materializes every lane's KV via
        re-prefill, so a reset executor is exactly as good as a new one
        for restoring a snapshot — minus the allocation cost."""
        self.pool = SS.clear_pool(self.pool)
        self._last_mass = {}

    def decode_width(self, n_active: int) -> int:
        """The batch width a decode tick with `n_active` lanes computes at:
        the smallest covering bucket when compacting, else the full pool."""
        if not self.compact:
            return self.n_lanes
        return _cover(max(int(n_active), 1), self.lane_buckets)

    @partial(annotate_function, name="executor.decode")
    def decode(self, tokens: Sequence[int], positions: Sequence[int],
               tables: Sequence[Sequence[int]], lanes=None) -> List[int]:
        if self.compact and lanes is not None:
            return self._decode_compact(tokens, positions, tables, lanes)
        decode_step = self._steps()[1]
        with TraceAnnotation("executor.decode.pack"):
            if lanes is not None:
                # Full-width decode still computes every lane row; rows NOT
                # in `lanes` (empty slots, lanes mid-chunk-prefill) are
                # made INERT (pos -1, empty table) so their write lands in
                # the scratch block / is dropped instead of clobbering live
                # KV through a mid-prefill lane's real block table.
                act = set(int(i) for i in lanes)
                pad = _pad_token(self.cfg)
                tokens = [t if i in act else pad
                          for i, t in enumerate(tokens)]
                positions = [p if i in act else -1
                             for i, p in enumerate(positions)]
                tables = [t if i in act else []
                          for i, t in enumerate(tables)]
            t = np.asarray(list(tokens), np.int32)[:, None]
            p = np.asarray(list(positions), np.int32)
            tbl = self._table_array(tables, self.n_lanes)
        with TraceAnnotation("executor.decode.launch"):
            logits, self.pool, mass = decode_step(
                self.params, jnp.asarray(t), jnp.asarray(p),
                jnp.asarray(tbl), self.pool, context=self.context)
        with TraceAnnotation("executor.decode.pull"):
            if mass is not None:
                m = np.asarray(mass)
                act = lanes if lanes is not None else range(len(m))
                self._last_mass = {int(i): m[int(i)] for i in act}
            return np.asarray(jnp.argmax(logits, axis=-1)).astype(
                int).tolist()

    def _decode_compact(self, tokens, positions, tables, lanes) -> List[int]:
        """Pack the active lanes into the smallest covering bucket and run
        the compacted step: padding rows carry lane id n_lanes (their
        per-lane write-back is dropped) and an all -1 table (they read and
        write only the scratch block)."""
        compact_step = self._steps()[3]
        with TraceAnnotation("executor.decode.pack"):
            w = self.decode_width(len(lanes))
            mb = _cover(max((len(tables[i]) for i in lanes), default=1),
                        self.table_buckets)
            t = np.zeros((w, 1), np.int32)
            p = np.zeros((w,), np.int32)
            lane_arr = np.full((w,), self.n_lanes, np.int32)
            tbl = np.full((w, mb), -1, np.int32)
            for j, i in enumerate(lanes):
                t[j, 0] = tokens[i]
                p[j] = positions[i]
                lane_arr[j] = i
                if len(tables[i]) > mb:
                    raise ValueError(f"lane {i}: table of {len(tables[i])} "
                                     f"blocks exceeds bucketed width {mb}")
                tbl[j, :len(tables[i])] = tables[i]
        with TraceAnnotation("executor.decode.launch"):
            logits, self.pool, mass = compact_step(
                self.params, jnp.asarray(t), jnp.asarray(p),
                jnp.asarray(tbl), jnp.asarray(lane_arr), self.pool,
                context=self.context)
        with TraceAnnotation("executor.decode.pull"):
            if mass is not None:
                m = np.asarray(mass)
                self._last_mass = {int(i): m[j]
                                   for j, i in enumerate(lanes)}
            out = np.asarray(jnp.argmax(logits, axis=-1))
            res = [0] * self.n_lanes
            for j, i in enumerate(lanes):
                res[i] = int(out[j])
            return res

    @partial(annotate_function, name="executor.chunk")
    def prefill_chunks(self, lanes: Sequence[int],
                       chunks: Sequence[Sequence[int]],
                       starts: Sequence[int],
                       tables: Optional[Sequence[Sequence[int]]] = None,
                       final: Optional[Sequence[bool]] = None) -> List[int]:
        """One batched chunk-prefill call: lane `lanes[j]` appends prompt
        tokens `chunks[j]` at absolute positions starting at `starts[j]`
        through its block table. Short final chunks pad with position -1
        (masked everywhere); returned next-token ids are meaningful only
        where `final[j]`."""
        if not self.chunk:
            raise RuntimeError("executor built with chunk=0")
        chunk_step = self._steps()[4]
        C = self.chunk
        with TraceAnnotation("executor.chunk.pack"):
            w = _cover(len(lanes), self.lane_buckets)
            tbls = [list(t) for t in (tables if tables is not None else
                                      [[]] * len(lanes))]
            mb = _cover(max((len(t) for t in tbls), default=1),
                        self.table_buckets)
            toks = np.full((w, C), _pad_token(self.cfg), np.int32)
            pos = np.full((w, C), -1, np.int32)
            lane_arr = np.full((w,), self.n_lanes, np.int32)
            tbl = self._table_array(tbls + [[]] * (w - len(tbls)), w,
                                    width=mb)
            for j, lane in enumerate(lanes):
                c = list(chunks[j])
                if not 0 < len(c) <= C:
                    raise ValueError(f"lane {lane}: chunk of {len(c)} "
                                     f"tokens vs chunk size {C}")
                toks[j, :len(c)] = c
                pos[j, :len(c)] = starts[j] + np.arange(len(c))
                lane_arr[j] = lane
        with TraceAnnotation("executor.chunk.launch"):
            logits, self.pool = chunk_step(
                self.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(tbl), jnp.asarray(lane_arr), self.pool,
                context=self.context)
        self.chunk_calls += 1
        with TraceAnnotation("executor.chunk.pull"):
            out = np.asarray(jnp.argmax(logits, axis=-1))
            return [int(out[j]) for j in range(len(lanes))]

    def block_masses(self) -> Dict[int, np.ndarray]:
        """Per-lane attention mass over the lane's logical blocks from the
        last decode tick ({} when mass tracking is off) — the retention
        policy's ranking signal."""
        return self._last_mass

    def compile_counts(self) -> dict:
        prefill_step, decode_step, reset_step, compact_step, chunk_step = \
            self._steps()
        return {"prefill": _compile_count(prefill_step),
                "decode": _compile_count(decode_step),
                "decode_compact": _compile_count(compact_step),
                "chunk": _compile_count(chunk_step),
                "reset": _compile_count(reset_step)}
