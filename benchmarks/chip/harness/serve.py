"""The system under test, built through its normal path, warmed and driven.

plan_serving -> Engine -> PagedJaxExecutor(compact=True, chunk=...), with
the attention backend `serving_settings()` picks (the compiled Pallas
kernels on a TPU). The engine has no public per-tick API, so `drive`
calls `Engine._start` once and `Engine._step` per tick, appending each due
request to the run state's `pending` queue with the current tick as its
arrival. Those two methods and the `_RunState` fields read here (`pending`,
`queue`, `slots`, `completions`, `useful`, `decode_ticks`) are part of the
yardstick.

Arrivals are on the wall clock. A request is timed from its due time; a
token from the end of the tick that produced it, when the host holds it.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation


class CompileClock:
    """Counts XLA compiles (a load from the persistent cache included) and
    their seconds through jax.monitoring; `events` totals every timed
    event (tracing, lowering, compiling) by name."""

    def __init__(self):
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.seconds = 0.0
        self.events: Dict[str, List[float]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        tot = self.events.setdefault(event, [0, 0.0])
        tot[0] += 1
        tot[1] += duration
        if event == self.event:
            self.count += 1
            self.seconds += duration

    def summary(self) -> str:
        return " ".join(f"{k.rsplit('/', 1)[-1]}={n}x{s:.1f}s"
                        for k, (n, s) in sorted(self.events.items()))


@dataclass
class Call:
    """One executor call as the engine made it: kind, host start and end,
    and the work it asked for (per lane: decode (position, table blocks);
    chunk (start, valid tokens, final); prefill prompt length)."""
    kind: str
    t0: float
    t1: float
    work: list
    traced: bool = False


class Recorder:
    """Stands between the engine and the executor: records every call and,
    while a trace is taken, marks it with a host span."""

    def __init__(self, executor):
        self._ex = executor
        self.calls: List[Call] = []
        self.annotate = False

    def __getattr__(self, name):
        return getattr(self._ex, name)

    def _run(self, kind, work, fn, *args, **kw):
        span = (TraceAnnotation(f"bench.exec.{kind}") if self.annotate
                else contextlib.nullcontext())
        t0 = time.perf_counter()
        with span:
            out = fn(*args, **kw)
        self.calls.append(Call(kind, t0, time.perf_counter(), work,
                               self.annotate))
        return out

    def decode(self, tokens, positions, tables, lanes=None):
        work = [(positions[i], len(tables[i])) for i in lanes]
        return self._run("decode", work, self._ex.decode, tokens, positions,
                         tables, lanes=lanes)

    def prefill_chunks(self, lanes, chunks, starts, tables=None, final=None):
        work = [(s, len(c), bool(f))
                for s, c, f in zip(starts, chunks, final)]
        return self._run("chunk", work, self._ex.prefill_chunks, lanes,
                         chunks, starts, tables=tables, final=final)

    def prefill_batch(self, lanes, prompts, tables):
        work = [len(p) for p in prompts]
        return self._run("prefill", work, self._ex.prefill_batch, lanes,
                         prompts, tables)

    def fresh_blocks(self, ids):
        return self._run("reset", [len(ids)], self._ex.fresh_blocks, ids)


@dataclass
class System:
    cfg: object                  # the program's ModelConfig
    engine: object
    executor: object
    recorder: Recorder
    n_lanes: int
    n_blocks: int
    kv_block: int
    chunk: int
    prefill_budget: int
    context: int
    promised_bytes: float        # Eq. 11 requirement the plan promises
    stack: contextlib.ExitStack = field(default_factory=contextlib.ExitStack)
    phases: Dict[str, float] = field(default_factory=dict)  # set-up seconds


def build(config: Dict, traffic, seed: int, devices, adapter) -> System:
    """Plan the deployment for the traffic's length profile, draw the
    weights from the seed and build the engine, as `launch/serve.py` does
    for `--kv paged --compact --chunk-prefill`."""
    from repro import hw as HW
    from repro.configs.base import DECODE, ShapeConfig
    from repro.core import measure as MM
    from repro.core.predictor import MemoryPlan
    from repro.parallel import sharding as SH
    from repro.parallel.axes import axis_rules
    from repro.search import execplan as XP
    from repro.search import space as SP
    from repro.serving.executor import PagedJaxExecutor

    t0 = time.perf_counter()
    sv = config["serving"]
    cfg = adapter.program_config(config)
    kv_block = sv["kv_block"]
    context = -(-traffic.context() // kv_block) * kv_block
    shape = ShapeConfig("bench", DECODE, context, sv["max_lanes"])
    hw = (HW.for_device_kind(devices[0].device_kind)
          if devices[0].platform == "tpu" else HW.TPU_V5E)
    host = XP.host_execution(cfg, shape, MemoryPlan(), len(devices),
                             sv["model_parallel"])
    pinned = SP.serving_space(
        cfg, shape, max_devices=len(devices),
        data=(host.mesh_shape.get("data", 1),),
        model=(host.mesh_shape.get("model", 1),),
        kv_blocks=(kv_block,), kv_quants=("none",), kv_retains=(0,))
    _, splan = XP.plan_serving(
        cfg, shape, n_devices=len(devices), hw=hw,
        measurer=MM.SimulatedMeasurer(host.mesh_shape), space=pinned,
        kv="paged", kv_blocks=(kv_block,), seq_lens=traffic.plan_lengths(),
        compact=True, prefill_kernel="tiled", chunk=sv["chunk"],
        prefill_budget=sv["prefill_budget"])
    n_lanes = splan.slots(cap=sv["max_lanes"])
    if n_lanes < 1:
        raise RuntimeError("the planner admits no lane under the budget")
    n_blocks = splan.pool_blocks(n_lanes, context)
    t1 = time.perf_counter()
    mesh, strategy = splan.execution.build(devices)
    if mesh.shape.get("model", 1) != sv["model_parallel"]:
        raise RuntimeError(
            f"the mesh {dict(mesh.shape)} splits a layer over "
            f"{mesh.shape.get('model', 1)} devices; the configuration "
            f"states model_parallel {sv['model_parallel']}")
    stack = contextlib.ExitStack()
    stack.enter_context(mesh)
    stack.enter_context(axis_rules(strategy.rules(), mesh=mesh))
    abstract = adapter.abstract_params(config)
    params = jax.block_until_ready(adapter.program_params(
        config, seed, SH.to_named(mesh, SH.param_specs(cfg, abstract,
                                                       strategy, mesh))))
    t2 = time.perf_counter()
    executor = PagedJaxExecutor(params, cfg, n_lanes=n_lanes,
                                n_blocks=n_blocks, kv_block=splan.kv_block,
                                context=context, compact=True,
                                chunk=sv["chunk"])
    jax.block_until_ready(executor.pool)
    promised = (splan.hbm_budget - hw.reserved_bytes) / HW.CAPACITY_HEADROOM
    sysm = System(cfg=cfg, engine=None, executor=executor,
                  recorder=Recorder(executor), n_lanes=n_lanes,
                  n_blocks=n_blocks, kv_block=splan.kv_block,
                  chunk=sv["chunk"], prefill_budget=sv["prefill_budget"],
                  context=context, promised_bytes=promised, stack=stack,
                  phases={"plan": t1 - t0, "weights": t2 - t1,
                          "pool": time.perf_counter() - t2})
    fresh_engine(sysm)
    return sysm


def fresh_engine(sysm: System) -> None:
    """A new engine with an empty block ledger over the system's executor
    (whose pool the caller has cleared or never used)."""
    from repro.serving import BlockAllocator, Engine
    sysm.engine = Engine(sysm.recorder, sysm.n_lanes,
                         allocator=BlockAllocator(sysm.n_blocks,
                                                  sysm.kv_block,
                                                  reservation="worst"),
                         chunk_prefill=sysm.chunk,
                         prefill_budget=sysm.prefill_budget)


def _cover(n: int, buckets) -> int:
    return next(b for b in buckets if b >= n)


def warm_shapes(sysm: System, traffic) -> Dict[str, list]:
    """Every shape the traffic can reach: (lane bucket, table bucket) of
    compact decode and of chunked prefill, the whole-prompt prefill lengths
    and the block-reset widths. A chunk tick takes lanes in order while
    their chunks fit the prefill budget, so it holds at most budget //
    (smallest chunk) lanes; the smallest chunk is the shortest final chunk
    of any prompt."""
    bs, C = sysm.kv_block, sysm.chunk
    ex = sysm.executor
    dec, chk = set(), set()
    smallest = C
    for p, o in traffic.pairs:
        # decode writes positions p .. p + o - 2 (the first token comes
        # from prefill); the lane then holds pos // bs + 1 blocks
        for pos in list(range(p, p + o - 1, bs)) + [p + o - 2]:
            if pos >= p:
                dec.add(_cover(pos // bs + 1, ex.table_buckets))
        if p > C:
            smallest = min(smallest, p - C * ((p - 1) // C))
            for k in range(-(-p // C)):
                chk.add(_cover(-(-min(p, (k + 1) * C) // bs),
                               ex.table_buckets))
    most = min(sysm.n_lanes, max(1, sysm.prefill_budget // smallest))
    lanes = list(ex.lane_buckets)
    prefill = sorted({p for p, _ in traffic.pairs if p <= C})
    return {"decode": sorted(dec), "chunk": sorted(chk),
            "prefill": prefill, "lanes": lanes,
            "chunk_lanes": [w for w in lanes if w <= _cover(most, lanes)]}


def warm_up(sysm: System, traffic) -> int:
    """Run every shape the window can use once, through the executor's own
    calls (so each jitted step's in-memory cache holds it), then clear the
    pool. Returns the number of calls made."""
    ex = sysm.executor
    shapes = warm_shapes(sysm, traffic)
    L, bs = sysm.n_lanes, sysm.kv_block
    ids = list(range(1, sysm.n_blocks + 1))

    def table(i, n):
        return [ids[(i * n + j) % len(ids)] for j in range(n)]

    calls = 0
    lap_start = [time.perf_counter()]

    def lap(kind):
        jax.block_until_ready(ex.pool)
        now = time.perf_counter()
        sysm.phases[f"warm_{kind}"] = now - lap_start[0]
        lap_start[0] = now

    for w in shapes["lanes"]:
        lanes = list(range(w))
        for t in shapes["decode"]:
            tables = [table(i, t) if i < w else [] for i in range(L)]
            pos = [t * bs - 1 if i < w else 0 for i in range(L)]
            ex.decode([0] * L, pos, tables, lanes=lanes)
            calls += 1
    lap("decode")
    for w in shapes["chunk_lanes"]:
        lanes = list(range(w))
        for t in shapes["chunk"]:
            start = t * bs - sysm.chunk
            ex.prefill_chunks(lanes, [[0] * sysm.chunk] * w, [start] * w,
                              tables=[table(i, t) for i in lanes],
                              final=[True] * w)
            calls += 1
    lap("chunk")
    for p in shapes["prefill"]:
        ex.prefill_batch([0], [[0] * p], [table(0, -(-p // bs))])
        calls += 1
    lap("prefill")
    # a tick re-links at most a block per decoding lane and C / bs blocks
    # per chunk lane; fresh_blocks pads to a multiple of the lane count
    most = L + shapes["chunk_lanes"][-1] * (sysm.chunk // bs)
    for k in range(-(-most // L)):
        ex.fresh_blocks(ids[:k * L + 1])
        calls += 1
    ex.reset()
    lap("reset")
    return calls


@dataclass
class Req:
    due: float
    prompt: Tuple[int, ...]
    max_new: int
    stamps: List[float] = field(default_factory=list)
    tokens: Optional[Tuple[int, ...]] = None      # set when finished


@dataclass
class Tick:
    t0: float
    t1: float
    exec_s: float


@dataclass
class Drive:
    """What one run offered and saw."""
    reqs: Dict[int, Req]
    ticks: List[Tick]
    window: Tuple[float, float]
    counters: Dict[str, Tuple[int, int]]   # name -> (at window start, end)
    compiles_in_window: int
    loadgen_late_s: List[float] = field(default_factory=list)


def drive(sysm: System, traffic, seconds: float, clock: CompileClock,
          trace_dir: Optional[str] = None, trace_s: float = 8.0) -> Drive:
    """Ramp, measure for `seconds`, then (open loop) drain until every
    request due in the window has its first token or `drain_max_s` has
    passed. With `trace_dir`, a profiler trace of about `trace_s` seconds
    is taken in the middle of the window, between ticks."""
    from repro.serving.trace import Request
    spec = traffic.spec
    eng, rec = sysm.engine, sysm.recorder
    st = eng._start([])
    reqs: Dict[int, Req] = {}
    seen: Dict[int, int] = {}
    ticks: List[Tick] = []
    late: List[float] = []
    n_done = 0
    t_org = time.perf_counter()
    ws = t_org + spec["ramp_s"]
    we = ws + seconds
    drain_end = we + spec.get("drain_max_s", 0.0)
    tr0 = ws + max(0.0, (seconds - trace_s) / 2)
    tr1 = tr0 + min(trace_s, seconds)
    tracing = traced = False
    counters = {}
    compiles0 = None
    closed_counters = False
    outstanding = spec.get("outstanding_per_lane", 0) * sysm.n_lanes
    if traffic.open:
        arrivals = traffic.arrivals(seconds, spec["ramp_s"])
        off, size = next(arrivals)
        next_due = ws + off
    else:
        next_due = drain_end

    def submit(due, size):
        rid = len(reqs)
        p, o = size
        req = Req(due=due, prompt=traffic.tokens(rid, p), max_new=o)
        reqs[rid] = req
        seen[rid] = 0
        st.pending.append(Request(rid=rid, arrival=st.tick,
                                  prompt=req.prompt, max_new=o))

    def busy():
        return bool(st.pending or st.queue
                    or any(s is not None for s in st.slots))

    def snap():
        return {"useful": st.useful, "decode_ticks": st.decode_ticks}

    def finished(now):
        # an open loop's window is over once every request due in it has
        # been offered (the last may still wait for a tick to end) and has
        # its first token
        if now < we:
            return False
        return (not traffic.open or now >= drain_end
                or (next_due >= we and all(r.stamps for r in reqs.values()
                                           if ws <= r.due < we)))

    while True:
        now = time.perf_counter()
        if compiles0 is None and now >= ws:
            compiles0 = clock.count
            counters = {k: [v, v] for k, v in snap().items()}
        if now >= we and len(counters) and not closed_counters:
            for k, v in snap().items():
                counters[k][1] = v
            closed_counters = True
        if finished(now):
            break
        if trace_dir is not None and not traced and now >= tr0:
            jax.profiler.start_trace(trace_dir)
            tracing = rec.annotate = traced = True
        span = (TraceAnnotation("bench.loadgen") if tracing
                else contextlib.nullcontext())
        with span:
            if traffic.open:
                while next_due <= now:
                    submit(next_due, size)
                    late.append(now - next_due)
                    off, size = next(arrivals)
                    next_due = ws + off
            elif now < we:
                while len(reqs) - n_done < outstanding:
                    submit(now, traffic.next_size())
        if not busy():
            time.sleep(max(0.0, min(next_due, drain_end) - now))
            continue
        n_calls = len(rec.calls)
        span = (TraceAnnotation("bench.tick") if tracing
                else contextlib.nullcontext())
        with span:
            t0 = time.perf_counter()
            eng._step(st)
            t1 = time.perf_counter()
        ticks.append(Tick(t0, t1, sum(c.t1 - c.t0
                                      for c in rec.calls[n_calls:])))
        for a in st.slots:
            if a is not None and len(a.tokens) > seen[a.req.rid]:
                r = a.req.rid
                reqs[r].stamps += [t1] * (len(a.tokens) - seen[r])
                seen[r] = len(a.tokens)
        for c in st.completions[n_done:]:
            r = reqs[c.rid]
            r.stamps += [t1] * (len(c.tokens) - seen[c.rid])
            seen[c.rid] = len(c.tokens)
            r.tokens = tuple(c.tokens)
        n_done = len(st.completions)
        if tracing and t1 >= tr1:
            jax.profiler.stop_trace()
            tracing = rec.annotate = False
    if tracing:
        jax.profiler.stop_trace()
        rec.annotate = False
    return Drive(reqs=reqs, ticks=ticks, window=(ws, we),
                 counters={k: tuple(v) for k, v in counters.items()},
                 compiles_in_window=clock.count - compiles0,
                 loadgen_late_s=late)
