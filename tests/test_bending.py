"""Capacity bending, hermetic tier: per-block KV quantization codecs
(int8 / nibble-packed int4 with per-row absmax scales), block-granular
retention on the device-free allocator and scripted engine (evicted blocks
reusable, never double-freed, shared prefix blocks immune), the bending
knobs of `serving_space`, and `plan_serving`'s minimum-agreement gate on
the quality/capacity frontier. Token parity of the REAL bent executor
against `greedy_generate` lives in the slow tier and the serving
benchmark's measured agreement column."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import DECODE, ShapeConfig
from repro.core import measure as MM
from repro.core import predictor as PR
from repro.core import profiler as PF
from repro.models import attention as ATT
from repro.search import execplan as XP
from repro.search import space as SP
from repro.serving import (BlockAllocator, Engine, Request,
                           ScriptedExecutor)

CFG = get_config("mistral-nemo-12b")
SHAPE = ShapeConfig("bend_t", DECODE, 4096, 8)
GIB = 2**30
VOCAB = 97


@pytest.fixture(scope="module")
def cls():
    sim = MM.SimulatedMeasurer({"data": 8})
    return PF.classify_workload(CFG, SHAPE, None, n_points=2, base_seq=64,
                                measurer=sim)


# --- quantization codec ------------------------------------------------------

@pytest.mark.parametrize("kind", ["int8", "int4"])
def test_quantize_roundtrip_error_bound(kind):
    """|dequant - x| <= scale/2 per element (absmax rounding), and the
    packed layout has the advertised width (int4: two codes per byte)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(scale=4.0, size=(5, 8, 3, 16)), jnp.float32)
    q, scale = ATT.quantize_kv(x, kind)
    assert scale.shape == x.shape[:-1]
    assert q.shape[-1] == (16 if kind == "int8" else 8)
    dq = ATT.dequantize_kv(q, scale, kind, dtype=jnp.float32)
    err = np.abs(np.asarray(dq) - np.asarray(x))
    bound = np.asarray(scale)[..., None] / 2 + 1e-5
    assert (err <= bound).all(), float((err - bound).max())


def test_quantize_zero_rows_exact():
    x = jnp.zeros((2, 3, 4, 8), jnp.float32)
    for kind in ("int8", "int4"):
        q, scale = ATT.quantize_kv(x, kind)
        dq = ATT.dequantize_kv(q, scale, kind, dtype=jnp.float32)
        assert np.asarray(dq).max() == 0.0 and np.asarray(scale).max() == 0.0


def test_quantize_roundtrip_error_bound_property():
    """Hypothesis pin: the per-row bound holds for arbitrary magnitudes
    (tiny, huge, mixed-sign), both codecs, any even head width."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed (optional test dep)")
    given = hypothesis.given
    st = hypothesis.strategies

    @hypothesis.settings(max_examples=40, deadline=None)
    @given(st.sampled_from(["int8", "int4"]),
           st.integers(1, 6),                      # rows
           st.sampled_from([2, 4, 6, 16]),         # head width (even)
           st.floats(1e-4, 1e4),                   # magnitude
           st.integers(0, 2**31 - 1))
    def run(kind, rows, hd, mag, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.uniform(-mag, mag, size=(rows, hd)), jnp.float32)
        q, scale = ATT.quantize_kv(x, kind)
        dq = ATT.dequantize_kv(q, scale, kind, dtype=jnp.float32)
        err = np.abs(np.asarray(dq) - np.asarray(x))
        s = np.asarray(scale)[..., None]
        assert (err <= s / 2 + 1e-6 * mag + 1e-30).all()

    run()


def test_quant_kind_is_self_describing():
    """The pool's dtype IS the codec: no side-channel flag to desync."""
    pool = {"kb": jnp.zeros((2, 4, 1, 8), jnp.bfloat16)}
    assert ATT.paged_quant_kind(pool) == "none"
    pool8 = {"kb": jnp.zeros((2, 4, 1, 8), jnp.int8), "ks": 0}
    assert ATT.paged_quant_kind(pool8) == "int8"
    pool4 = {"kb": jnp.zeros((2, 4, 1, 4), jnp.uint8), "ks": 0}
    assert ATT.paged_quant_kind(pool4) == "int4"


# --- predictor: quantized block bytes ---------------------------------------

def test_kv_block_bytes_shrink_with_quant():
    plans = {q: PR.MemoryPlan(kv_block_size=64, kv_quant=q)
             for q in ("none", "int8", "int4")}
    mesh = {"data": 4, "model": 1}
    bb = {q: PR.kv_block_bytes_per_device(CFG, SHAPE, p, mesh)
          for q, p in plans.items()}
    assert bb["none"] > bb["int8"] > bb["int4"]
    # scale stripes keep int8 above a naive /2 (and int4 above /4)
    assert bb["int8"] > bb["none"] / 2
    assert bb["int4"] > bb["none"] / 4


def test_quantized_blocks_raise_capacity(cls):
    mesh = {"data": 4, "model": 1}
    caps = {}
    for q in ("none", "int8"):
        plan = PR.MemoryPlan(kv_block_size=64, kv_quant=q)
        caps[q] = PR.serving_block_capacity(CFG, SHAPE, plan, cls, mesh,
                                            hbm_budget=12 * GIB)
    assert caps["int8"] > caps["none"]


# --- allocator: retention eviction ------------------------------------------

def test_free_block_returns_block_for_reuse():
    a = BlockAllocator(3, block_size=2)
    a.reserve(0, 3)
    ids = [a.alloc(0) for _ in range(3)]     # pool fully drained
    a.free_block(0, ids[1])
    assert a.in_use == 2
    assert a.alloc(0) == ids[1]              # the dropped block comes back
    a.free(0)
    assert a.free_blocks == 3


def test_free_block_double_free_raises():
    a = BlockAllocator(4, block_size=2)
    a.reserve(0, 2)
    bid = a.alloc(0)
    a.free_block(0, bid)
    with pytest.raises(RuntimeError, match="double free"):
        a.free_block(0, bid)
    with pytest.raises(RuntimeError, match="owns no blocks"):
        a.free_block(7, bid)                 # rid never reserved


def test_free_block_rejects_shared_prefix_blocks():
    """Prefix blocks are refcounted, never request-owned: retention must
    not be able to pull them out from under other sharers."""
    a = BlockAllocator(6, block_size=2)
    pfx = a.create_prefix("sys", 2)
    a.reserve(0, 2)
    a.alloc(0)
    with pytest.raises(RuntimeError, match="shared prefix"):
        a.free_block(0, pfx[0])


# --- engine: block-granular retention ---------------------------------------

def _req(rid, prompt_len=4, max_new=4, prefix=None):
    prompt = tuple((3 + rid * 5 + i) % (VOCAB - 2) + 2
                   for i in range(prompt_len))
    if prefix is not None:
        prompt = tuple(prefix) + prompt
    return Request(rid=rid, arrival=0, prompt=prompt, max_new=max_new,
                   prefix_id=(0 if prefix is not None else None),
                   prefix_len=(len(prefix) if prefix is not None else 0))


def _tokens(report):
    return {c.rid: list(c.tokens) for c in report.completions}


def test_retention_drops_blocks_and_caps_footprint():
    trace = [_req(r, prompt_len=4, max_new=16) for r in range(3)]

    def run(retain, pool=40):
        alloc = BlockAllocator(pool, 4)
        rep = Engine(ScriptedExecutor(VOCAB), n_slots=3, allocator=alloc,
                     kv_retain=retain).run(trace)
        assert alloc.free_blocks == pool     # everything returned, once
        return rep

    plain, kept = run(0), run(1)
    assert _tokens(kept) == _tokens(plain)   # scheduling is undisturbed
    assert plain.block_drops == 0
    assert kept.block_drops > 0
    assert kept.peak_blocks < plain.peak_blocks
    assert "block_drops=" in kept.describe()


def test_retention_admits_more_under_tight_pool():
    """The bend pays rent: a pool too small for three exact 5-block
    sequences serves them concurrently once cold blocks are dropped."""
    from repro.serving import length_stats
    trace = [_req(r, prompt_len=4, max_new=16) for r in range(3)]
    pool = 9                                  # 3 lanes x (retain 1 + tail)
    stats = length_stats(trace)

    def run(retain):
        # expected-mode reservations: retention caps each lane's expected
        # own-block demand at retain+1, so admission sees the bend
        rep = Engine(ScriptedExecutor(VOCAB), n_slots=3,
                     allocator=BlockAllocator(pool, 4,
                                              reservation="expected"),
                     stats=stats, sigma_k=0.0,
                     kv_retain=retain).run(trace, max_ticks=20_000)
        return rep

    kept = run(1)
    plain = run(0)
    assert _tokens(kept) == _tokens(plain)
    assert kept.max_concurrent > plain.max_concurrent


def test_retention_never_drops_shared_prefix_blocks():
    prefix = tuple(2 + (i * 11) % (VOCAB - 2) for i in range(8))
    trace = [_req(r, prompt_len=4, max_new=12, prefix=prefix)
             for r in range(4)]
    block, pool = 4, 24
    alloc = BlockAllocator(pool, block)
    report = Engine(ScriptedExecutor(VOCAB), n_slots=4, allocator=alloc,
                    chunk_prefill=block, prefix_share=True,
                    kv_retain=1).run(trace)
    assert len(report.completions) == len(trace)
    assert report.block_drops > 0
    # no leak: everything is free or sitting in the reclaimable prefix cache
    assert alloc.available_blocks == pool
    roomy = Engine(ScriptedExecutor(VOCAB), n_slots=4,
                   allocator=BlockAllocator(64, block),
                   chunk_prefill=block, prefix_share=True).run(trace)
    assert _tokens(report) == _tokens(roomy)


def test_engine_retention_requires_allocator():
    with pytest.raises(ValueError, match="kv_retain"):
        Engine(ScriptedExecutor(VOCAB), n_slots=2, kv_retain=1)


# --- search space: bending knobs and legality -------------------------------

def test_serving_space_bending_knobs():
    space = SP.serving_space(CFG, SHAPE, max_devices=1, data=(1,), model=(1,),
                             kv_blocks=(64,), kv_quants=("none", "int8"),
                             kv_retains=(0, 4))
    combos = {(c.plan.kv_quant, c.plan.kv_retain)
              for c in space.candidates(CFG, SHAPE)}
    assert combos == {("none", 0), ("none", 4), ("int8", 0), ("int8", 4)}


def test_bending_needs_paged_pool():
    space = SP.serving_space(CFG, SHAPE, max_devices=1, data=(1,), model=(1,),
                             kv_blocks=(0,),           # ring
                             kv_quants=("none", "int8"), kv_retains=(0, 4))
    combos = {(c.plan.kv_quant, c.plan.kv_retain)
              for c in space.candidates(CFG, SHAPE)}
    assert combos == {("none", 0)}           # quant/retain filtered on ring


def test_int4_needs_even_head_dim():
    odd = dataclasses.replace(CFG, head_dim=63)
    space = SP.serving_space(odd, SHAPE, max_devices=1, data=(1,), model=(1,),
                             kv_blocks=(64,), kv_quants=("int8", "int4"))
    quants = {c.plan.kv_quant for c in space.candidates(odd, SHAPE)}
    assert quants == {"int8"}


def test_memory_plan_validates_bend():
    with pytest.raises(ValueError):
        PR.MemoryPlan(kv_quant="fp7")
    with pytest.raises(ValueError):
        PR.MemoryPlan(kv_retain=-1)


# --- planner: the quality/capacity frontier ---------------------------------

def test_plan_serving_agreement_gate(cls):
    lens = [60] * 7 + [2000]
    kw = dict(n_devices=4, cls=cls, hbm_budget=12 * GIB, kv="paged",
              seq_lens=lens, kv_quants=("none", "int8", "int4"),
              kv_retains=(0, 4))
    _, free = XP.plan_serving(CFG, SHAPE, **kw)
    _, exact = XP.plan_serving(CFG, SHAPE, min_agreement=1.0, **kw)
    _, gated = XP.plan_serving(CFG, SHAPE, min_agreement=0.99, **kw)
    # unconstrained search bends; the gate walks back along the frontier
    assert free.execution.plan.kv_quant != "none"
    assert free.capacity >= gated.capacity >= exact.capacity
    assert exact.execution.plan.kv_quant == "none"
    assert exact.execution.plan.kv_retain == 0
    assert exact.agreement == 1.0
    assert gated.agreement >= 0.99
    assert gated.execution.plan.kv_quant == "int8"
    assert "agreement>=" in gated.describe()


def test_plan_serving_gate_unreachable_raises(cls):
    with pytest.raises(ValueError, match="min_agreement"):
        XP.plan_serving(CFG, SHAPE, n_devices=4, cls=cls,
                        hbm_budget=12 * GIB, kv="paged", seq_lens=(2000,),
                        kv_quants=("int4",), kv_retains=(0,),
                        min_agreement=0.999)


def test_predicted_agreement_priors():
    p8 = PR.MemoryPlan(kv_block_size=64, kv_quant="int8")
    p4r = PR.MemoryPlan(kv_block_size=64, kv_quant="int4", kv_retain=3)
    assert XP.predicted_agreement(PR.MemoryPlan(kv_block_size=64), 10) == 1.0
    assert XP.predicted_agreement(p8, 10) == XP.QUANT_AGREEMENT["int8"]
    # retention prior only bites when the cap binds (retain+1 < blocks)
    assert XP.predicted_agreement(p4r, 4) == XP.QUANT_AGREEMENT["int4"]
    assert XP.predicted_agreement(p4r, 10) < XP.QUANT_AGREEMENT["int4"]
