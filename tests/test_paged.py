"""Paged KV block pool, hermetic tier: block-granular capacity inversion
(predictor.serving_block_capacity), the paged planning mode of
plan_serving, the device-free BlockAllocator, the paged engine scheduling
core (admission by actual footprint, block reuse, determinism), the
engine-level batched prefill, and eval_shape pins of the paged pool steps
— all with ZERO XLA compiles. Token parity of the real paged executor
against greedy_generate lives in the slow tier (test_serve.py)."""
import dataclasses

import jax
import pytest

from repro import hw as HW
from repro.configs import get_config
from repro.configs.base import DECODE, ShapeConfig
from repro.core import measure as MM
from repro.core import predictor as PR
from repro.core import profiler as PF
from repro.search import execplan as XP
from repro.search import space as SP
from repro.serving import (BlockAllocator, Engine, Request, ScriptedExecutor,
                           synthetic_trace, trace_context)

CFG = get_config("mistral-nemo-12b")         # pure global attn: all layers page
SHAPE = ShapeConfig("paged_t", DECODE, 4096, 8)
GIB = 2**30


def _cls(cfg=CFG, shape=SHAPE):
    sim = MM.SimulatedMeasurer({"data": 8})
    return PF.classify_workload(cfg, shape, None, n_points=2, base_seq=64,
                                measurer=sim)


@pytest.fixture(scope="module")
def cls():
    return _cls()


def _no_compile(monkeypatch):
    import repro.launch.compile as LC

    def boom(*a, **k):
        raise AssertionError("XLA compile attempted in hermetic test")
    monkeypatch.setattr(LC, "build", boom)


# --- block math: the requirement model at block granularity ------------------

def test_block_bytes_tile_the_ring_exactly(cls):
    """ceil(context / block) KV blocks must cost exactly one whole-sequence
    ring (+ the per-lane fixed state) when the block tiles the context —
    the block pool re-cuts the same bytes, it doesn't invent new ones."""
    mesh = {"data": 1, "model": 1}
    for block in (64, 256):
        plan = PR.MemoryPlan(kv_block_size=block)
        assert SHAPE.context % block == 0
        per_block = PR.kv_block_bytes_per_device(CFG, SHAPE, plan, mesh)
        lane = PR.lane_bytes_per_device(CFG, SHAPE, plan, mesh)
        one = dataclasses.replace(SHAPE, global_batch=1)
        ring = PR.cache_bytes_per_device(CFG, one, plan, mesh)
        n_blocks = SHAPE.context // block
        assert per_block > 0
        assert per_block * n_blocks + lane == pytest.approx(ring)


def test_block_bytes_kv_shard_aware():
    """'heads' pads 2 kv heads up to a whole replicated head over model=4;
    'seq' shards the block's positions — the block accounting must see the
    same difference serving_capacity does."""
    cfg = dataclasses.replace(CFG, name="nemo-kv2", n_kv_heads=2)
    mesh = {"data": 1, "model": 4}
    heads = PR.kv_block_bytes_per_device(
        cfg, SHAPE, PR.MemoryPlan(kv_shard="heads", kv_block_size=64), mesh)
    seq = PR.kv_block_bytes_per_device(
        cfg, SHAPE, PR.MemoryPlan(kv_shard="seq", kv_block_size=64), mesh)
    assert heads > seq > 0


def test_serving_block_capacity_is_exact(cls):
    """The returned block count fits the budget and one more per-device
    block does not — the inversion is exact w.r.t. the forward terms."""
    mesh = {"data": 2, "model": 2}
    plan = PR.MemoryPlan(kv_block_size=64)
    budget = 24 * GIB
    lanes = 4
    nb = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=lanes,
                                   hbm_budget=budget)
    _, dp, _ = PR.mesh_factors(mesh)
    assert nb > 0 and nb % dp == 0

    sh = dataclasses.replace(SHAPE, global_batch=lanes * dp)
    base = (PR.resident_bytes(CFG, sh, plan, mesh)
            - PR.cache_bytes_per_device(CFG, sh, plan, mesh)
            + lanes * PR.lane_bytes_per_device(CFG, sh, plan, mesh))
    tra = PR.transient_bytes(CFG, sh, plan, cls, mesh)
    per_block = PR.kv_block_bytes_per_device(CFG, SHAPE, plan, mesh)

    def capacity_at(blocks_per_device):
        return HW.capacity_from_requirement(
            base + blocks_per_device * per_block, tra)

    assert capacity_at(nb // dp) <= budget
    assert capacity_at(nb // dp + 1) > budget


def test_serving_block_capacity_monotone_and_bounds(cls):
    mesh = {"data": 1, "model": 1}
    plan = PR.MemoryPlan(kv_block_size=64)
    caps = [PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh,
                                      lanes=2, hbm_budget=b * GIB)
            for b in (38, 40, 48, 64)]
    assert caps == sorted(caps)
    assert caps[-1] > caps[0] > 0
    # nothing fits a toy budget
    assert PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=1,
                                     hbm_budget=2**20) == 0
    # more lanes eat the block budget
    few = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=1,
                                    hbm_budget=48 * GIB)
    many = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=8,
                                     hbm_budget=48 * GIB)
    assert few > many > 0
    with pytest.raises(ValueError, match="kv_block_size"):
        PR.serving_block_capacity(CFG, SHAPE, PR.MemoryPlan(), cls, mesh)
    with pytest.raises(ValueError, match="lanes"):
        PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=0)


def test_serving_block_capacity_avg_context_frees_blocks(cls):
    """Paged decode reads through block tables, so a short expected reach
    shrinks the per-lane transient and leaves more budget for blocks."""
    mesh = {"data": 1, "model": 1}
    plan = PR.MemoryPlan(kv_block_size=64)
    worst = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=8,
                                      hbm_budget=48 * GIB)
    short = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, lanes=8,
                                      hbm_budget=48 * GIB, avg_context=128)
    assert short > worst > 0


# --- plan_serving(kv="paged"): expected concurrency over the block pool -----

def test_plan_serving_paged_zero_compiles(monkeypatch, cls):
    _no_compile(monkeypatch)
    lens = [60] * 7 + [2000]                 # mostly-short traffic
    got, splan = XP.plan_serving(CFG, SHAPE, n_devices=4, cls=cls,
                                 hbm_budget=12 * GIB, kv="paged",
                                 seq_lens=lens)
    assert got is cls
    assert splan.capacity > 0
    assert splan.kv_block in XP.DEFAULT_KV_BLOCKS
    assert splan.blocks > 0
    assert "kv_block=" in splan.describe()
    assert splan.slots(cap=3) == 3


def test_plan_serving_paged_beats_ring_2x(cls):
    """Acceptance pin (planner level): under a budget that admits exactly
    two worst-case ring slots, the paged planner admits >= 2x the
    concurrency on a mostly-short length distribution."""
    mesh = {"data": 1, "model": 1}

    def req(n):
        sh = dataclasses.replace(SHAPE, global_batch=n)
        return PR.predict(CFG, sh, PR.MemoryPlan(), cls, mesh).capacity_bytes

    budget = (req(2) + req(3)) / 2

    def pinned(kv_blocks):
        return SP.serving_space(CFG, SHAPE, max_devices=1, data=(1,),
                                model=(1,), kv_blocks=kv_blocks)

    _, ring = XP.plan_serving(CFG, SHAPE, n_devices=1, cls=cls,
                              hbm_budget=budget, space=pinned((0,)))
    lens = [60] * 7 + [SHAPE.context]
    _, paged = XP.plan_serving(CFG, SHAPE, n_devices=1, cls=cls,
                               hbm_budget=budget, space=pinned((64, 256)),
                               kv="paged", seq_lens=lens)
    assert ring.capacity == 2
    assert paged.capacity >= 2 * ring.capacity
    assert paged.blocks >= paged.capacity    # enough blocks to cover lanes


def test_serving_space_kv_block_knob():
    space = SP.serving_space(CFG, SHAPE, max_devices=4,
                             kv_blocks=(64, SHAPE.context * 2))
    sizes = {c.plan.kv_block_size for c in space.candidates(CFG, SHAPE)}
    assert sizes == {64}                     # oversize block filtered out


# --- BlockAllocator: the device-free free list ------------------------------

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(6, block_size=4)
    a.reserve(1, 3)
    ids = [a.alloc(1) for _ in range(3)]
    assert ids == [1, 2, 3]                  # id 0 is the scratch block
    assert a.in_use == 3 and a.committed == 3
    a.reserve(2, 3)
    assert not a.can_admit(1)                # fully committed
    assert a.free(1) == ids
    assert a.committed == 3 and a.in_use == 0
    b2 = [a.alloc(2) for _ in range(3)]
    assert b2 == [4, 5, 6]                   # FIFO reuse order
    a.free(2)
    assert a.free_blocks == 6
    assert a.peak_in_use == 3
    assert a.peak_committed == 6


def test_allocator_guards():
    with pytest.raises(ValueError, match="n_blocks"):
        BlockAllocator(0, 4)
    with pytest.raises(ValueError, match="block_size"):
        BlockAllocator(4, 0)
    a = BlockAllocator(4, 4)
    a.reserve(1, 2)
    with pytest.raises(RuntimeError, match="already holds"):
        a.reserve(1, 1)
    with pytest.raises(RuntimeError, match="over-commits"):
        a.reserve(2, 3)
    a.alloc(1)
    a.alloc(1)
    with pytest.raises(RuntimeError, match="reservation"):
        a.alloc(1)                           # beyond its reservation


def test_allocator_blocks_for():
    a = BlockAllocator(8, block_size=4)
    # written positions = prompt + max_new - 1
    assert a.blocks_for(Request(0, 0, (1,) * 4, 1)) == 1     # 4 -> 1 block
    assert a.blocks_for(Request(0, 0, (1,) * 4, 2)) == 2     # 5 -> 2
    assert a.blocks_for(Request(0, 0, (1,) * 8, 9)) == 4     # 16 -> 4


# --- the paged scheduling core ----------------------------------------------

def _burst(n, gens, seed=0, prompts=(4, 8)):
    return synthetic_trace(n, vocab_size=97, seed=seed, prompt_lens=prompts,
                           gen_lens=gens, mean_interarrival=0)


def test_paged_engine_matches_ring_engine_tokens():
    """The paged pool changes WHERE cache bytes live, never WHAT the model
    emits: scripted ring and paged runs produce identical completions."""
    trace = _burst(8, (2, 4, 8))
    ring = Engine(ScriptedExecutor(), 3).run(trace)
    paged = Engine(ScriptedExecutor(), 3,
                   allocator=BlockAllocator(16, 4)).run(trace)
    assert ([c.tokens for c in ring.completions]
            == [c.tokens for c in paged.completions])
    assert paged.n_blocks == 16
    assert 0 < paged.peak_blocks <= 16


def test_paged_admission_bounded_by_blocks_not_lanes():
    """With ample lanes but a tight block pool, the allocator is the
    admission controller: concurrency stops at what the blocks cover."""
    trace = _burst(6, (8,), prompts=(8,))    # each needs ceil(15/4)=4 blocks
    alloc = BlockAllocator(9, 4)             # room for exactly 2 at a time
    rep = Engine(ScriptedExecutor(), 6, allocator=alloc).run(trace)
    assert rep.max_concurrent == 2
    assert rep.peak_blocks <= 9
    assert len(rep.completions) == 6         # block reuse drains the queue
    assert alloc.committed == 0 and alloc.free_blocks == 9


def test_paged_engine_deterministic():
    trace = _burst(7, (1, 3, 9), seed=5)
    r1 = Engine(ScriptedExecutor(), 3,
                allocator=BlockAllocator(12, 4)).run(trace)
    r2 = Engine(ScriptedExecutor(), 3,
                allocator=BlockAllocator(12, 4)).run(trace)
    assert r1 == r2


def test_paged_engine_rejects_oversized_request():
    eng = Engine(ScriptedExecutor(), 2, allocator=BlockAllocator(2, 4))
    with pytest.raises(ValueError, match="never be admitted"):
        eng.run([Request(rid=0, arrival=0, prompt=(5,) * 8, max_new=9)])


def test_paged_acceptance_2x_concurrency_end_to_end(cls):
    """Acceptance pin, hermetic: plan ring and paged under the SAME tight
    budget, size engines from the plans, replay the same mostly-short
    trace — the paged engine runs >= 2x the concurrent sequences and
    completes identically."""
    mesh = {"data": 1, "model": 1}
    trace = synthetic_trace(12, vocab_size=97, seed=7, prompt_lens=(4, 8),
                            gen_lens=(4, 4, 8, 248), mean_interarrival=0.5)
    context = trace_context(trace)
    shape = ShapeConfig("paged_e2e", DECODE, context, 8)
    cls2 = _cls(CFG, shape)

    def req(n):
        sh = dataclasses.replace(shape, global_batch=n)
        return PR.predict(CFG, sh, PR.MemoryPlan(), cls2,
                          mesh).capacity_bytes

    budget = (req(2) + req(3)) / 2

    def pinned(kv_blocks):
        return SP.serving_space(CFG, shape, max_devices=1, data=(1,),
                                model=(1,), kv_blocks=kv_blocks)

    _, ring = XP.plan_serving(CFG, shape, n_devices=1, cls=cls2,
                              hbm_budget=budget, space=pinned((0,)))
    _, paged = XP.plan_serving(
        CFG, shape, n_devices=1, cls=cls2, hbm_budget=budget,
        space=pinned((4, 8, 16)), kv="paged",
        seq_lens=[len(r.prompt) + r.max_new - 1 for r in trace])

    ring_rep = Engine(ScriptedExecutor(), ring.slots(cap=len(trace))).run(trace)
    lanes = paged.slots(cap=len(trace))
    per_seq = -(-context // paged.kv_block)
    n_blocks = min(paged.blocks, lanes * per_seq)
    paged_rep = Engine(ScriptedExecutor(), lanes,
                       allocator=BlockAllocator(n_blocks,
                                                paged.kv_block)).run(trace)
    assert paged_rep.max_concurrent >= 2 * ring_rep.max_concurrent
    assert ([c.tokens for c in ring_rep.completions]
            == [c.tokens for c in paged_rep.completions])
    assert paged_rep.ticks <= ring_rep.ticks


# --- engine-level batched prefill -------------------------------------------

def test_batched_prefill_shares_calls_per_bucket():
    """6 burst requests, one prompt bucket, 4 slots: the first tick admits
    4 in ONE prefill call; stragglers backfill with at most one call per
    admission tick — calls strictly fewer than admissions."""
    trace = _burst(6, (4,), prompts=(4,))
    ex = ScriptedExecutor()
    rep = Engine(ex, 4).run(trace)
    assert rep.prefills == 6
    assert ex.prefill_batches == rep.prefill_calls
    assert rep.prefill_calls <= 3            # 1 burst call + <= 2 backfills
    assert rep.prefill_calls < rep.prefills


def test_batched_prefill_groups_by_bucket():
    """Same-tick admissions in DIFFERENT buckets stay separate calls (one
    padded compile shape per bucket)."""
    trace = [Request(rid=0, arrival=0, prompt=(3,) * 4, max_new=2),
             Request(rid=1, arrival=0, prompt=(3,) * 8, max_new=2),
             Request(rid=2, arrival=0, prompt=(4,) * 4, max_new=2)]
    ex = ScriptedExecutor()
    rep = Engine(ex, 4).run(trace)
    assert rep.prefills == 3
    assert rep.prefill_calls == 2            # buckets {4, 8}
    # token functions are per-request, so batching never changes outputs
    solo = [ScriptedExecutor().prefill(0, r.prompt) for r in trace]
    assert [c.tokens[0] for c in rep.completions] == solo


# --- shape pins (jax.eval_shape: trace only, no compiles) -------------------

def test_init_paged_pool_shapes():
    from repro.runtime import serve_step as SS
    cfg = get_config("gemma3-12b").reduced()   # window=8 locals + global
    lanes, n_blocks, block, context = 3, 9, 4, 16
    pool = SS.init_paged_pool(cfg, lanes, n_blocks, block, context,
                              abstract=True)
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    paged = ring = 0
    for i, blk in enumerate(cfg.unit):
        leaf = pool["units"][i]
        if SS.is_paged_block(blk, context):
            paged += 1
            assert leaf["kb"].shape == (cfg.repeats, n_blocks, block, K, hd)
            assert leaf["pos"].shape == (cfg.repeats, n_blocks, block)
        else:
            ring += 1
            L = blk.cache_len(context)
            assert leaf["k"].shape == (cfg.repeats, lanes, L, K, hd)
    assert paged >= 1 and ring >= 1          # the mixed tree is exercised
    with pytest.raises(ValueError, match="multiple"):
        SS.init_paged_pool(cfg, lanes, n_blocks, 5, context)


def test_paged_steps_preserve_pool_shapes():
    import jax.numpy as jnp

    from repro.models import init_params
    from repro.runtime import serve_step as SS
    cfg = get_config("gemma3-12b").reduced()
    lanes, n_blocks, block, context = 2, 7, 4, 16
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_params(key, cfg))
    pool = SS.init_paged_pool(cfg, lanes, n_blocks, block, context,
                              abstract=True)
    shapes = jax.tree.map(lambda a: a.shape, pool)

    prefill = SS.make_paged_prefill_step(cfg)
    tokens = jax.ShapeDtypeStruct((lanes, 4), jnp.int32)
    lane_ids = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    tables = jax.ShapeDtypeStruct((lanes, context // block), jnp.int32)
    logits, new_pool = jax.eval_shape(
        lambda p, t, l, tb, P: prefill(p, t, l, tb, P, context=context),
        params, tokens, lane_ids, tables, pool)
    assert jax.tree.map(lambda a: a.shape, new_pool) == shapes
    assert logits.shape == (lanes, cfg.padded_vocab_size)

    decode = SS.make_paged_decode_step(cfg)
    tok1 = jax.ShapeDtypeStruct((lanes, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    logits, new_pool, mass = jax.eval_shape(
        lambda p, t, po, tb, P: decode(p, t, po, tb, P, context=context),
        params, tok1, pos, tables, pool)
    assert jax.tree.map(lambda a: a.shape, new_pool) == shapes
    assert logits.shape == (lanes, cfg.padded_vocab_size)
    assert mass is None                   # track_mass off by default

    ids = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    reset = jax.eval_shape(SS.reset_pool_blocks, pool, ids)
    assert jax.tree.map(lambda a: a.shape, reset) == shapes


def test_batch_prefill_step_shapes():
    import jax.numpy as jnp

    from repro.models import init_params
    from repro.models import model as M
    from repro.runtime import serve_step as SS
    cfg = get_config("recurrentgemma-9b").reduced()   # attn + recurrent mix
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_params(key, cfg))
    pool = M.init_cache(cfg, 3, 16, abstract=True)
    step = SS.make_batch_prefill_step(cfg)
    tokens = jax.ShapeDtypeStruct((3, 4), jnp.int32)
    slots = jax.ShapeDtypeStruct((3,), jnp.int32)
    logits, new_pool = jax.eval_shape(
        lambda p, t, s, P: step(p, t, s, P, context=16),
        params, tokens, slots, pool)
    assert jax.tree.map(lambda a: a.shape, new_pool) \
        == jax.tree.map(lambda a: a.shape, pool)
    assert logits.shape == (3, cfg.padded_vocab_size)


def test_compact_decode_step_shapes():
    """The compacted decode gathers w < lanes per-lane rows, runs at width
    w, and scatters back: pool shapes are preserved exactly, logits come
    out at the COMPACTED width, and trimmed block tables (narrower than
    the pool max) are accepted — all traced, zero compiles."""
    import jax.numpy as jnp

    from repro.models import init_params
    from repro.runtime import serve_step as SS
    cfg = get_config("gemma3-12b").reduced()
    lanes, n_blocks, block, context = 4, 9, 4, 16
    w, mb = 2, 2                               # compacted width, trimmed table
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_params(key, cfg))
    pool = SS.init_paged_pool(cfg, lanes, n_blocks, block, context,
                              abstract=True)
    shapes = jax.tree.map(lambda a: a.shape, pool)

    compact = SS.make_compact_decode_step(cfg)
    tok = jax.ShapeDtypeStruct((w, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((w,), jnp.int32)
    tables = jax.ShapeDtypeStruct((w, mb), jnp.int32)
    lane_ids = jax.ShapeDtypeStruct((w,), jnp.int32)
    logits, new_pool, mass = jax.eval_shape(
        lambda p, t, po, tb, l, P: compact(p, t, po, tb, l, P,
                                           context=context),
        params, tok, pos, tables, lane_ids, pool)
    assert jax.tree.map(lambda a: a.shape, new_pool) == shapes
    assert logits.shape == (w, cfg.padded_vocab_size)
    assert mass is None


def test_chunk_prefill_step_appends_in_place():
    """The chunk-prefill step consumes [w, C] mid-prompt tokens against the
    live pool and returns it shape-identical (blocks written through the
    tables, rings in place) with per-row last-valid logits."""
    import jax.numpy as jnp

    from repro.models import init_params
    from repro.runtime import serve_step as SS
    cfg = get_config("gemma3-12b").reduced()
    lanes, n_blocks, block, context = 3, 9, 4, 16
    w, C, mb = 2, 8, 4
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_params(key, cfg))
    pool = SS.init_paged_pool(cfg, lanes, n_blocks, block, context,
                              abstract=True)
    shapes = jax.tree.map(lambda a: a.shape, pool)

    chunk = SS.make_chunk_prefill_step(cfg)
    tok = jax.ShapeDtypeStruct((w, C), jnp.int32)
    pos = jax.ShapeDtypeStruct((w, C), jnp.int32)
    tables = jax.ShapeDtypeStruct((w, mb), jnp.int32)
    lane_ids = jax.ShapeDtypeStruct((w,), jnp.int32)
    logits, new_pool = jax.eval_shape(
        lambda p, t, po, tb, l, P: chunk(p, t, po, tb, l, P,
                                         context=context),
        params, tok, pos, tables, lane_ids, pool)
    assert jax.tree.map(lambda a: a.shape, new_pool) == shapes
    assert logits.shape == (w, cfg.padded_vocab_size)


def test_gather_scatter_pool_lanes_roundtrip_shapes():
    """gather narrows every per-lane leaf to width w (paged leaves pass
    through untouched); scatter restores the full pool shape."""
    import jax.numpy as jnp

    from repro.runtime import serve_step as SS
    cfg = get_config("gemma3-12b").reduced()
    lanes, w = 4, 2
    pool = SS.init_paged_pool(cfg, lanes, 9, 4, 16, abstract=True)
    ids = jax.ShapeDtypeStruct((w,), jnp.int32)
    sub = jax.eval_shape(SS.gather_pool_lanes, pool, ids)
    for P, S in zip(pool["units"], sub["units"]):
        if SS._is_paged_leaf(P):
            assert jax.tree.map(lambda a: a.shape, S) \
                == jax.tree.map(lambda a: a.shape, P)
        else:
            for a, b in zip(jax.tree.leaves(P), jax.tree.leaves(S)):
                assert b.shape == (a.shape[0], w) + a.shape[2:]
    back = jax.eval_shape(SS.scatter_pool_lanes, pool, sub, ids)
    assert jax.tree.map(lambda a: a.shape, back) \
        == jax.tree.map(lambda a: a.shape, pool)


# --- executor-side compaction knobs (no compiles: constructor validation) ---

def test_paged_executor_bucket_and_chunk_validation():
    from repro.serving.executor import (PagedJaxExecutor, _cover,
                                        _pow2_buckets)
    assert _pow2_buckets(8) == (1, 2, 4, 8)
    assert _pow2_buckets(6) == (1, 2, 4, 6)    # n_lanes appended as cap
    assert _cover(3, (1, 2, 4)) == 4
    assert _cover(5, (1, 2, 4)) == 4           # clamps at the top bucket
    cfg = get_config("mistral-nemo-12b").reduced()
    params = None                              # constructor-only: never used
    with pytest.raises(ValueError, match="kv_block"):
        PagedJaxExecutor(params, cfg, n_lanes=2, n_blocks=8, kv_block=4,
                         context=16, chunk=6)
    # recurrent mixers now ride the chunked path (scan state carried in
    # per-lane pool leaves) — the constructor accepts them and flags the
    # tree so the engine can refuse prefix sharing
    rg = get_config("recurrentgemma-9b").reduced()
    ex = PagedJaxExecutor(params, rg, n_lanes=2, n_blocks=8, kv_block=4,
                          context=16, chunk=4)
    assert ex.has_recurrent
    attn_only = PagedJaxExecutor(params, cfg, n_lanes=2, n_blocks=8,
                                 kv_block=4, context=16, chunk=4)
    assert not attn_only.has_recurrent


def test_prefix_share_refuses_recurrent_mixers():
    """Shared prefix blocks carry attention KV only — a recurrent arch
    cannot resume a sharer mid-prompt, so the engine refuses upfront."""
    from repro.serving import BlockAllocator
    from repro.serving.engine import Engine
    from repro.serving.executor import PagedJaxExecutor
    rg = get_config("recurrentgemma-9b").reduced()
    ex = PagedJaxExecutor(None, rg, n_lanes=2, n_blocks=8, kv_block=4,
                          context=16, chunk=4)
    with pytest.raises(ValueError, match="prefix_share is attention-only"):
        Engine(ex, 2, allocator=BlockAllocator(8, 4), chunk_prefill=4,
               prefix_share=True)


# --- prefill as a first-class capacity term ---------------------------------

def test_prefill_transient_tiled_below_dense(cls):
    """The tiled flash-prefill kernel never materializes the
    O(chunk x context) score matrix or a dequantized fp copy of the
    gathered context, so its modeled transient must sit strictly below the
    dense jnp oracle's — and only the DENSE term may grow with reach."""
    mesh = {"data": 1, "model": 1}
    plan = PR.MemoryPlan(kv_block_size=64)
    kw = dict(prefill_tokens=64, mode="paper")
    dense = PR.prefill_transient_bytes(CFG, SHAPE, plan, cls, mesh,
                                       reach=4096, kernel="dense", **kw)
    tiled = PR.prefill_transient_bytes(CFG, SHAPE, plan, cls, mesh,
                                       reach=4096, kernel="tiled", **kw)
    assert dense > tiled > 0
    dense_short = PR.prefill_transient_bytes(CFG, SHAPE, plan, cls, mesh,
                                             reach=256, kernel="dense", **kw)
    tiled_short = PR.prefill_transient_bytes(CFG, SHAPE, plan, cls, mesh,
                                             reach=256, kernel="tiled", **kw)
    assert dense_short < dense
    assert tiled_short == tiled


def test_serving_block_capacity_charges_prefill_transient(cls):
    """Under a short expected reach (the regime paged serving plans for)
    a context-sized prefill burst raises the transient peak above the
    decode term, so blocks shrink — and the dense oracle (score matrix +
    fp gather) loses strictly more of them than the tiled kernel."""
    mesh = {"data": 1, "model": 1}
    plan = PR.MemoryPlan(kv_block_size=64)
    kw = dict(lanes=8, hbm_budget=48 * GIB, avg_context=128)
    base = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh, **kw)
    tiled = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh,
                                      prefill_tokens=SHAPE.context,
                                      prefill_kernel="tiled", **kw)
    dense = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh,
                                      prefill_tokens=SHAPE.context,
                                      prefill_kernel="dense", **kw)
    assert base >= tiled > dense > 0
    # a token-budgeted prefill (small chunked transient) costs less than
    # admitting the whole prompt in one dense burst
    budgeted = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh,
                                         prefill_tokens=64,
                                         prefill_kernel="tiled", **kw)
    assert budgeted >= tiled


def test_plan_serving_prefill_budget_knob(monkeypatch, cls):
    """plan_serving threads the prefill budget and kernel through to the
    capacity term and the returned ServingPlan; the budget is searchable
    as a serving-space knob; misuse raises before any planning work."""
    _no_compile(monkeypatch)
    lens = [60] * 7 + [2000]
    kw = dict(n_devices=4, cls=cls, hbm_budget=12 * GIB, kv="paged",
              seq_lens=lens)
    _, splan = XP.plan_serving(CFG, SHAPE, chunk=8, prefill_budget=16,
                               prefill_kernel="tiled", **kw)
    assert splan.prefill_budget == 16
    assert splan.prefill_kernel == "tiled"
    assert "prefill_budget=16" in splan.describe()
    _, dense = XP.plan_serving(CFG, SHAPE, chunk=8, prefill_budget=16,
                               prefill_kernel="dense", **kw)
    assert splan.capacity >= dense.capacity > 0
    # searched as a knob: the chosen budget is one of the candidates and
    # the lattice actually widened
    _, plain = XP.plan_serving(CFG, SHAPE, **kw)
    assert plain.prefill_budget == 0
    assert "prefill_budget" not in plain.describe()
    _, searched = XP.plan_serving(CFG, SHAPE, chunk=8,
                                  prefill_budgets=(16, 256), **kw)
    assert searched.prefill_budget in (16, 256)
    assert searched.considered == 2 * plain.considered
    with pytest.raises(ValueError, match="needs chunk > 0"):
        XP.plan_serving(CFG, SHAPE, prefill_budget=16, **kw)
    with pytest.raises(ValueError, match="unknown prefill_kernel"):
        XP.plan_serving(CFG, SHAPE, chunk=8, prefill_kernel="bogus", **kw)


def test_engine_prefill_budget_validation():
    with pytest.raises(ValueError, match="prefill_budget"):
        Engine(ScriptedExecutor(), 2, prefill_budget=-1,
               chunk_prefill=4, allocator=BlockAllocator(8, 4))
    with pytest.raises(ValueError, match="needs chunk_prefill"):
        Engine(ScriptedExecutor(), 2, prefill_budget=4)


def test_prefill_budget_token_identical_and_accounted():
    """The token budget changes WHEN chunks land, never WHAT tokens come
    out: a tightly budgeted run completes identically to the unbudgeted
    chunked run, spreads the chunk work over more calls, counts every
    prompt token exactly once in prefill_tokens, and keeps the tick
    taxonomy a partition."""
    trace = _burst(4, (2, 4), prompts=(8, 12))
    total_prompt = sum(len(r.prompt) for r in trace)

    def run(budget):
        ex = ScriptedExecutor()
        rep = Engine(ex, 4, allocator=BlockAllocator(24, 4),
                     chunk_prefill=4, prefill_budget=budget).run(trace)
        return ex, rep

    ex0, free = run(0)
    ex1, tight = run(4)              # one 4-token chunk per tick
    assert ([c.tokens for c in free.completions]
            == [c.tokens for c in tight.completions])
    assert free.prefill_tokens == tight.prefill_tokens == total_prompt
    assert free.prefill_throughput() > tight.prefill_throughput() > 0
    assert ex1.chunk_calls >= ex0.chunk_calls
    assert ex1.chunk_tokens == ex0.chunk_tokens
    assert tight.ticks > free.ticks  # the budget really throttled
    for rep in (free, tight):
        assert rep.ticks == (rep.decode_ticks + rep.admit_ticks
                             + rep.idle_ticks)


def test_prefill_budget_fair_share_tightest_slo_first():
    """Two same-length prompts in different SLO classes contend for a
    budget that admits ONE chunk per tick: the tighter class must reach
    its first token strictly earlier (round-robin leads with class 0)."""
    trace = [Request(rid=0, arrival=0, prompt=(3,) * 12, max_new=2, slo=1),
             Request(rid=1, arrival=0, prompt=(4,) * 12, max_new=2, slo=0)]
    rep = Engine(ScriptedExecutor(), 2, allocator=BlockAllocator(24, 4),
                 chunk_prefill=4, prefill_budget=4).run(trace)
    assert len(rep.completions) == 2
    by_rid = {c.rid: c for c in rep.completions}
    assert by_rid[1].first_token < by_rid[0].first_token


def test_report_percentiles_empty_without_completions():
    """Zero completions (an overload trace can evict everything before a
    first token) must yield empty percentile dicts and a describe() that
    still renders — not a KeyError at the report line."""
    from repro.serving.engine import ServeReport
    rep = ServeReport(policy="continuous", n_slots=2, completions=[],
                      ticks=5, decode_ticks=0, useful_slot_tokens=0,
                      idle_ticks=5, peak_queue=3, max_concurrent=0,
                      prefills=0)
    assert rep.latency_percentiles() == {}
    assert rep.ttft_percentiles() == {}
    assert rep.mean_ttft() == 0.0
    assert "lat_p50/p95/p99=-/-/-" in rep.describe()


def test_fused_prefill_avoids_dense_score_transient():
    """Jaxpr-level pin of the tentpole's memory claim: tracing the tiled
    kernel produces NO top-level intermediate as large as the
    O(chunk x context) score matrix, while the dense jnp oracle path
    materializes one at least that large (trace-only, zero compiles)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    b, C, K, G, hd = 1, 8, 2, 2, 16
    bs, mB, nB = 8, 64, 16                   # context 512 >> chunk 8
    ctx = mB * bs
    q = jax.ShapeDtypeStruct((b, C, K, G, hd), jnp.float32)
    kn = jax.ShapeDtypeStruct((b, C, K, hd), jnp.float32)
    kp = jax.ShapeDtypeStruct((nB, bs, K, hd), jnp.bfloat16)
    pp = jax.ShapeDtypeStruct((nB, bs), jnp.int32)
    tb = jax.ShapeDtypeStruct((b, mB), jnp.int32)
    pos = jax.ShapeDtypeStruct((b, C), jnp.int32)

    def tiled(q, kn, vn, kp, vp, pp, tb, pos):
        return kops.paged_prefill_attention(q, kn, vn, kp[None], vp[None],
                                            pp[None], tb, pos, layer=0,
                                            backend="interpret")

    def dense(q, kn, vn, kp, vp, pp, tb, pos):
        from repro.configs.base import BlockSpec
        from repro.models import attention as A
        cache = {"kb": kp[None], "vb": vp[None], "pos": pp[None]}
        return A._chunk_append(q, kn, vn, cache, BlockSpec(), pos, tb,
                               A.AttnSettings(backend="naive"), layer=0)

    score_elems = C * K * G * ctx            # the [C, heads, ctx] matrix

    def max_intermediate(fn):
        jaxpr = jax.make_jaxpr(fn)(q, kn, kn, kp, kp, pp, tb, pos).jaxpr
        return max(int(np.prod(v.aval.shape))
                   for eqn in jaxpr.eqns for v in eqn.outvars)

    assert max_intermediate(dense) >= score_elems
    assert max_intermediate(tiled) < score_elems


@pytest.mark.parametrize("backend", ["blocked", "pallas"])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_scanned_and_unrolled_paged_steps_agree(kv_quant, backend):
    """The layer scan carries the stacked pool and each layer updates it in
    place at its own index; the unrolled loop passes the same stack from
    layer to layer. On a 3-layer model both routes give identical logits,
    identical whole pools after a chunk tick and several decode ticks, and
    identical per-block attention mass, through the jnp paths and through
    the Pallas kernels (interpreted off-TPU)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import depth_variant
    from repro.models import attention as A
    from repro.models import init_params
    from repro.models import model as M
    from repro.runtime import serve_step as SS
    # f32 weights: with bf16 ones XLA's excess-precision rewrites round
    # the scan body and the unrolled layers differently (the routes then
    # differ by ~2e-3 in the logits whatever the cache does)
    cfg = dataclasses.replace(depth_variant(CFG.reduced(), 3),
                              param_dtype="float32")
    assert cfg.repeats == 3
    lanes, n_blocks, block, context, C = 3, 12, 4, 16, 6
    params = init_params(jax.random.PRNGKey(1), cfg)
    pool0 = SS.init_paged_pool(cfg, lanes, n_blocks, block, context,
                               kv_quant=kv_quant)
    # lanes 0 and 2 hold blocks; lane 1 stays empty (an inert row)
    tables = jnp.asarray([[1, 4, 7, -1], [-1] * 4, [2, 5, 9, 3]], jnp.int32)
    lane_ids = jnp.arange(lanes, dtype=jnp.int32)
    rng = np.random.default_rng(0)
    chunk_tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (lanes, C)),
                            jnp.int32)
    chunk_pos = jnp.asarray([list(range(C)), [-1] * C,
                             list(range(C - 2)) + [-1, -1]], jnp.int32)
    cursor = np.array([C, -1, C - 2], np.int32)

    def run(scan_layers):
        settings = M.ModelSettings(
            attn=A.AttnSettings(backend=backend, track_mass=True),
            scan_layers=scan_layers)
        chunk = jax.jit(SS.make_chunk_prefill_step(cfg, settings),
                        static_argnames=("context",))
        decode = jax.jit(SS.make_compact_decode_step(cfg, settings),
                         static_argnames=("context",))
        logits, pool = chunk(params, chunk_tok, chunk_pos, tables, lane_ids,
                             pool0, context=context)
        outs = [logits]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for t in range(4):
            pos = jnp.asarray(np.where(cursor >= 0, cursor + t, -1),
                              jnp.int32)
            logits, pool, mass = decode(params, tok[:, None], pos, tables,
                                        lane_ids, pool, context=context)
            outs += [logits, mass]
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return outs, pool

    outs_scan, pool_scan = run(True)
    outs_loop, pool_loop = run(False)
    for a, b in zip(outs_scan, outs_loop):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(pool_scan), jax.tree.leaves(pool_loop)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the ticks wrote every layer of the pool: live positions in layer 2
    written = np.asarray(pool_scan["units"][0]["pos"])[2]
    assert (written >= 0).sum() == (C + 4) + (C - 2 + 4)
