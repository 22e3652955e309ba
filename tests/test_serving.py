"""Memory-governed serving engine, hermetic tier: the capacity inversion
(predictor.serving_capacity), the max-concurrency planner (plan_serving),
and the device-free scheduler core (admission bound, queueing, continuous-vs-
static occupancy) — all with ZERO XLA compiles. Token parity of the real
executor against greedy_generate lives in the slow tier (test_serve.py)."""
import dataclasses

import jax
import pytest

from repro.configs import get_config
from repro.configs.base import DECODE, ShapeConfig
from repro.core import measure as MM
from repro.core import predictor as PR
from repro.core import profiler as PF
from repro.search import execplan as XP
from repro.search import space as SP
from repro.serving import (BlockAllocator, Engine, Request, ScriptedExecutor,
                           describe_trace, synthetic_trace, trace_context)

CFG = get_config("h2o-danube-1.8b")
SHAPE = ShapeConfig("serve_t", DECODE, 4096, 8)
GIB = 2**30


def _cls(cfg=CFG, shape=SHAPE):
    sim = MM.SimulatedMeasurer({"data": 8})
    return PF.classify_workload(cfg, shape, None, n_points=2, base_seq=64,
                                measurer=sim)


def _no_compile(monkeypatch):
    import repro.launch.compile as LC

    def boom(*a, **k):
        raise AssertionError("XLA compile attempted in hermetic test")
    monkeypatch.setattr(LC, "build", boom)


@pytest.fixture(scope="module")
def cls():
    return _cls()


# --- serving_capacity: the requirement model run backwards -------------------

def test_serving_capacity_is_exact_admission_bound(cls):
    """The returned count fits the budget; one more per-device slot does
    not — the inversion is exact w.r.t. the forward model."""
    mesh = {"data": 2, "model": 1}
    budget = 8 * GIB
    plan = PR.MemoryPlan()
    cap = PR.serving_capacity(CFG, SHAPE, plan, cls, mesh, hbm_budget=budget)
    assert cap > 0 and cap % 2 == 0          # whole per-device slots x dp
    _, dp, _ = PR.mesh_factors(mesh)

    def capacity_at(n):
        sh = dataclasses.replace(SHAPE, global_batch=n)
        return PR.predict(CFG, sh, plan, cls, mesh).capacity_bytes

    assert capacity_at(cap) <= budget
    assert capacity_at(cap + dp) > budget


def test_serving_capacity_monotone_in_budget(cls):
    mesh = {"data": 1, "model": 4}
    caps = [PR.serving_capacity(CFG, SHAPE, PR.MemoryPlan(), cls, mesh,
                                hbm_budget=b * GIB) for b in (3, 4, 8, 16)]
    assert caps == sorted(caps)
    assert caps[-1] > caps[0] > 0


def test_serving_capacity_zero_when_nothing_fits(cls):
    cap = PR.serving_capacity(CFG, SHAPE, PR.MemoryPlan(), cls,
                              {"data": 1}, hbm_budget=2**20)
    assert cap == 0


def test_serving_capacity_kv_seq_beats_padded_heads():
    """With 2 kv heads over model=4, 'heads' sharding pads each device up
    to a whole replicated head (2x the exact share) while 'seq' shards the
    ring length evenly — the admission controller must see the difference
    (why kv_shard is a real knob in serving_space)."""
    cfg = dataclasses.replace(CFG, name="h2o-kv2", n_kv_heads=2)
    cls = _cls(cfg, SHAPE)
    mesh = {"data": 1, "model": 4}
    heads = PR.serving_capacity(cfg, SHAPE, PR.MemoryPlan(kv_shard="heads"),
                                cls, mesh, hbm_budget=6 * GIB)
    seq = PR.serving_capacity(cfg, SHAPE, PR.MemoryPlan(kv_shard="seq"),
                              cls, mesh, hbm_budget=6 * GIB)
    assert seq > heads > 0


# --- plan_serving: pick the config that maximizes admitted concurrency ------

def test_plan_serving_zero_compiles(monkeypatch, cls):
    _no_compile(monkeypatch)
    got_cls, splan = XP.plan_serving(CFG, SHAPE, n_devices=8, cls=cls,
                                     hbm_budget=8 * GIB)
    assert got_cls is cls
    assert splan.capacity > 0
    assert splan.execution.schedule == "single"
    assert splan.execution.n_devices <= 8
    assert splan.considered > 1
    # more devices must never admit less
    _, splan1 = XP.plan_serving(CFG, SHAPE, n_devices=1, cls=cls,
                                hbm_budget=8 * GIB)
    assert splan.capacity >= splan1.capacity
    assert "capacity=" in splan.describe()


def test_plan_serving_beats_single_device_default(cls):
    """The planned mesh admits strictly more than the naive data:1 host
    default under a tight budget — the whole point of planning the mesh."""
    budget = 3 * GIB
    _, auto = XP.plan_serving(CFG, SHAPE, n_devices=8, cls=cls,
                              hbm_budget=budget)
    pinned = SP.serving_space(CFG, SHAPE, max_devices=8, data=(1,),
                              model=(1,))
    _, host = XP.plan_serving(CFG, SHAPE, n_devices=8, cls=cls,
                              hbm_budget=budget, space=pinned)
    assert auto.capacity > host.capacity
    assert auto.capacity >= 8                # the planned mesh fills the host


def test_serving_plan_slots_cap(cls):
    _, splan = XP.plan_serving(CFG, SHAPE, n_devices=8, cls=cls,
                               hbm_budget=8 * GIB)
    assert splan.slots() == splan.capacity
    assert splan.slots(cap=4) == 4
    assert splan.slots(cap=10**9) == splan.capacity


def test_serving_space_pins_serving_knobs():
    space = SP.serving_space(CFG, SHAPE, max_devices=8)
    for cand in space.candidates(CFG, SHAPE):
        assert cand.plan.remat == "none"
        assert cand.plan.microbatches == 1
        assert cand.mesh_shape["pipe"] == 1
        assert cand.plan.kv_shard in ("heads", "seq")


class _CountingMeasurer(MM.SimulatedMeasurer):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.n_measures = 0

    def _measure(self, *args, **kwargs):
        self.n_measures += 1
        return super()._measure(*args, **kwargs)


def test_auto_plan_threads_measurer():
    """`--backend compile` on the auto path must reach the provided
    measurer (satellite: the flag used to be silently ignored)."""
    counting = _CountingMeasurer({"data": 8})
    cls, eplan = XP.auto_plan(CFG, SHAPE, n_devices=8, measurer=counting)
    assert counting.n_measures > 0           # classification ladder used it
    assert eplan.n_devices <= 8


# --- synthetic traces --------------------------------------------------------

def test_synthetic_trace_deterministic():
    kw = dict(vocab_size=512, seed=3, prompt_lens=(4, 8), gen_lens=(2, 4),
              mean_interarrival=1.5)
    t1 = synthetic_trace(10, **kw)
    t2 = synthetic_trace(10, **kw)
    assert t1 == t2
    assert t1 != synthetic_trace(10, **{**kw, "seed": 4})
    arrivals = [r.arrival for r in t1]
    assert arrivals == sorted(arrivals)
    assert all(2 <= tok < 512 for r in t1 for tok in r.prompt)
    assert trace_context(t1) == max(len(r.prompt) + r.max_new for r in t1)
    assert "requests" in describe_trace(t1)


def test_synthetic_trace_burst_mode():
    t = synthetic_trace(5, vocab_size=64, seed=0, mean_interarrival=0)
    assert all(r.arrival == 0 for r in t)


# --- the scheduler core ------------------------------------------------------

def _burst(n, gens, seed=0, prompts=(4, 8)):
    return synthetic_trace(n, vocab_size=97, seed=seed, prompt_lens=prompts,
                           gen_lens=gens, mean_interarrival=0)


def test_admission_never_exceeds_slot_pool():
    """The deterministic occupancy pin: with 6 burst requests and a
    2-slot pool (the WSMC capacity bound), concurrency never exceeds 2,
    oversubscribed requests queue, and everything still completes."""
    trace = _burst(6, (2, 4, 8))
    rep = Engine(ScriptedExecutor(), 2).run(trace)
    assert rep.max_concurrent == 2
    assert rep.peak_queue >= 4               # 6 arrived, 2 admitted at t=0
    assert len(rep.completions) == 6
    assert rep.generated_tokens == sum(r.max_new for r in trace)
    for c in rep.completions:
        assert len(c.tokens) == trace[c.rid].max_new
        assert c.admitted >= c.arrival
        assert c.finished >= c.admitted


def test_admission_bound_derives_from_serving_capacity(cls):
    """Acceptance pin, end to end: the slot pool sized by the PREDICTED
    capacity bounds concurrency — admission never exceeds
    predictor.serving_capacity, the rest queue, everyone completes."""
    mesh = {"data": 1, "model": 4}
    cap = PR.serving_capacity(CFG, SHAPE, PR.MemoryPlan(), cls, mesh,
                              hbm_budget=8 * GIB)
    assert 0 < cap < 8                       # the budget is genuinely tight
    trace = _burst(cap + 4, (2, 4, 8))
    rep = Engine(ScriptedExecutor(), cap).run(trace)
    assert rep.max_concurrent <= cap
    assert rep.peak_queue > 0                # oversubscription queued
    assert len(rep.completions) == cap + 4


def test_engine_run_is_deterministic():
    trace = _burst(7, (1, 3, 9), seed=5)
    r1 = Engine(ScriptedExecutor(), 3).run(trace)
    r2 = Engine(ScriptedExecutor(), 3).run(trace)
    assert r1 == r2


def test_continuous_beats_static_occupancy():
    """Acceptance pin: on a mixed-length trace, continuous batching's
    useful-token fraction of decode-step slots is strictly higher than the
    fixed-batch baseline's (backfill vs straggler-pinned idle slots)."""
    trace = _burst(8, (2, 8))
    cont = Engine(ScriptedExecutor(), 3, policy="continuous").run(trace)
    stat = Engine(ScriptedExecutor(), 3, policy="static").run(trace)
    assert len(cont.completions) == len(stat.completions) == 8
    # same tokens generated either way (scheduling must not change outputs)
    assert ([c.tokens for c in cont.completions]
            == [c.tokens for c in stat.completions])
    assert cont.occupancy() > stat.occupancy()
    assert cont.ticks <= stat.ticks
    assert 0.0 < stat.occupancy() < cont.occupancy() <= 1.0


def test_single_token_requests_complete_without_decode():
    trace = _burst(4, (1,))
    rep = Engine(ScriptedExecutor(), 4).run(trace)
    assert len(rep.completions) == 4
    assert rep.decode_ticks == 0
    assert all(len(c.tokens) == 1 for c in rep.completions)
    # finishing at admission still counts as having been concurrent/busy
    assert rep.max_concurrent == 4
    assert rep.idle_ticks == 0


def test_staggered_arrivals_idle_then_serve():
    trace = [r for r in synthetic_trace(4, vocab_size=97, seed=1,
                                        prompt_lens=(4,), gen_lens=(2,),
                                        mean_interarrival=6.0)]
    rep = Engine(ScriptedExecutor(), 2).run(trace)
    assert len(rep.completions) == 4
    if trace[-1].arrival > 8:                # gaps => idle ticks counted
        assert rep.idle_ticks > 0


def test_engine_rejects_bad_config():
    with pytest.raises(ValueError, match="n_slots"):
        Engine(ScriptedExecutor(), 0)
    with pytest.raises(ValueError, match="policy"):
        Engine(ScriptedExecutor(), 2, policy="paged")


def test_engine_rejects_degenerate_requests():
    """max_new=0 / empty prompts must fail fast, not spin to max_ticks."""
    from repro.serving import Request
    eng = Engine(ScriptedExecutor(), 2)
    with pytest.raises(ValueError, match="max_new"):
        eng.run([Request(rid=0, arrival=0, prompt=(5,), max_new=0)])
    with pytest.raises(ValueError, match="prompt"):
        eng.run([Request(rid=0, arrival=0, prompt=(), max_new=2)])


def test_report_metrics_bounds():
    trace = _burst(5, (2, 4))
    rep = Engine(ScriptedExecutor(), 2).run(trace)
    assert 0.0 < rep.occupancy() <= 1.0
    assert rep.throughput() > 0
    assert rep.mean_latency() >= 0
    assert "occupancy=" in rep.describe()


# --- slot-aware prefill shapes (trace-only: jax.eval_shape, no compiles) ----

def test_prefill_cache_pads_to_full_ring():
    """A prompt shorter than cache_len must still emit the FULL ring (empty
    slots pos=-1) — shorter rings would wrap at prompt_len and evict live
    context, and pool slots need uniform shapes."""
    from repro.models import init_params
    from repro.models import model as M
    from repro.runtime.serve_step import make_prefill_step
    cfg = get_config("h2o-danube-1.8b").reduced()
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_params(key, cfg))
    tokens = jax.ShapeDtypeStruct((1, 4), jax.numpy.int32)
    prefill = make_prefill_step(cfg)
    _, cache = jax.eval_shape(lambda p, t: prefill(p, t, context=12),
                              params, tokens)
    ref = M.init_cache(cfg, 1, 12, abstract=True)
    assert jax.tree.map(lambda a: a.shape, cache) \
        == jax.tree.map(lambda a: a.shape, ref)


def test_write_cache_slot_preserves_pool_shapes():
    from repro.models import model as M
    from repro.runtime.serve_step import write_cache_slot
    cfg = get_config("recurrentgemma-9b").reduced()   # attn + recurrent mix
    pool = M.init_cache(cfg, 3, 16, abstract=True)
    one = M.init_cache(cfg, 1, 16, abstract=True)
    out = jax.eval_shape(lambda P, o: write_cache_slot(cfg, P, o, 1),
                         pool, one)
    assert jax.tree.map(lambda a: a.shape, out) \
        == jax.tree.map(lambda a: a.shape, pool)


# --- tick taxonomy (the metrics invariant the bugfix sweep pins) ------------

def test_tick_taxonomy_is_a_partition():
    """Every tick is exactly one of decode / admit-only / idle — the
    accounting identity ticks == decode + admit + idle must hold for
    bursty, staggered, and chunked schedules alike."""
    traces = [_burst(6, (2, 4)),
              synthetic_trace(5, vocab_size=97, seed=3, prompt_lens=(4, 8),
                              gen_lens=(1, 4), mean_interarrival=3.0),
              _burst(4, (1,))]
    for chunk in (0, 4):
        for trace in traces:
            rep = Engine(ScriptedExecutor(), 2,
                         allocator=BlockAllocator(24, 4),
                         chunk_prefill=chunk).run(trace)
            assert rep.ticks == (rep.decode_ticks + rep.admit_ticks
                                 + rep.idle_ticks)
            assert len(rep.completions) == len(trace)


def test_single_token_burst_counts_admit_not_idle():
    """Prefill-only traffic: the admission ticks must land in admit_ticks,
    never in idle_ticks (the engine was busy) nor decode_ticks (no decode
    step ran)."""
    rep = Engine(ScriptedExecutor(), 4).run(_burst(4, (1,)))
    assert rep.decode_ticks == 0
    assert rep.idle_ticks == 0
    assert rep.admit_ticks >= 1
    assert rep.ticks == rep.admit_ticks


# --- lane compaction (scripted: bucket selection and width accounting) ------

def test_compacted_decode_picks_covering_bucket():
    """With 2 active lanes in a 4-lane pool the engine must decode at
    width 2, and at width 1 once one lane remains — tick_widths records
    the smallest covering bucket each decode tick."""
    trace = [Request(rid=0, arrival=0, prompt=(3, 4), max_new=6),
             Request(rid=1, arrival=0, prompt=(5, 6), max_new=2)]
    ex = ScriptedExecutor(buckets=(1, 2, 4))
    rep = Engine(ex, 4, allocator=BlockAllocator(16, 4)).run(trace)
    assert set(ex.tick_widths) == {1, 2}
    assert ex.tick_widths == sorted(ex.tick_widths, reverse=True)
    assert rep.decode_lane_tokens == sum(ex.tick_widths)
    assert rep.decode_lane_tokens < rep.decode_ticks * 4
    assert rep.occupancy() > 0.9             # vs ~0.4 at full width


def test_compacted_and_full_width_tokens_identical():
    """Compaction changes WHICH lanes ride each decode step, never what
    any lane emits: bucketed and full-width scripted runs agree."""
    trace = _burst(8, (2, 4, 8), seed=11)
    full = Engine(ScriptedExecutor(), 4,
                  allocator=BlockAllocator(32, 4)).run(trace)
    ex = ScriptedExecutor(buckets=(1, 2, 4))
    comp = Engine(ex, 4, allocator=BlockAllocator(32, 4)).run(trace)
    assert ([c.tokens for c in full.completions]
            == [c.tokens for c in comp.completions])
    assert min(ex.tick_widths) < 4           # compaction actually engaged


def test_engine_width_accounting_without_decode_width():
    """Executors without decode_width (the ring JaxExecutor protocol) are
    charged full pool width — occupancy falls back to the old meaning."""
    rep = Engine(ScriptedExecutor(), 3).run(_burst(4, (2, 4)))
    assert rep.decode_lane_tokens == rep.decode_ticks * 3


# --- chunked prefill (scripted: scheduling and parity) ----------------------

def test_chunked_prefill_matches_whole_prompt_tokens():
    """Splitting a long prompt into chunks interleaved with decode ticks
    must not change any completion: same trace, chunked vs unchunked."""
    trace = synthetic_trace(6, vocab_size=97, seed=9, prompt_lens=(4, 20),
                            gen_lens=(2, 4), mean_interarrival=0.5)
    whole = Engine(ScriptedExecutor(), 3,
                   allocator=BlockAllocator(40, 4)).run(trace)
    ex = ScriptedExecutor()
    chunked = Engine(ex, 3, allocator=BlockAllocator(40, 4),
                     chunk_prefill=8).run(trace)
    assert ([c.tokens for c in whole.completions]
            == [c.tokens for c in chunked.completions])
    assert chunked.chunk_calls == ex.chunk_calls > 0
    assert chunked.ticks == (chunked.decode_ticks + chunked.admit_ticks
                             + chunked.idle_ticks)


def test_chunked_prefill_short_prompts_skip_chunking():
    """Prompts <= chunk_prefill take the whole-prompt path — zero chunk
    calls, identical schedule to chunk_prefill=0."""
    trace = _burst(4, (2,), prompts=(4,))
    ex = ScriptedExecutor()
    rep = Engine(ex, 2, allocator=BlockAllocator(16, 4),
                 chunk_prefill=8).run(trace)
    ref = Engine(ScriptedExecutor(), 2,
                 allocator=BlockAllocator(16, 4)).run(trace)
    assert ex.chunk_calls == 0 and rep.chunk_calls == 0
    assert rep.completions == ref.completions


def test_engine_rejects_misaligned_chunk():
    with pytest.raises(ValueError, match="multiple"):
        Engine(ScriptedExecutor(), 2, allocator=BlockAllocator(8, 4),
               chunk_prefill=6)
    with pytest.raises(ValueError, match=">= 0"):
        Engine(ScriptedExecutor(), 2, chunk_prefill=-1)
