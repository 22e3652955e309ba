"""Serving driver: replay a deterministic synthetic trace through the
memory-governed engine.

The driver is deliberately thin — all scheduling lives in
`repro.serving.Engine`, all capacity governance in
`search.execplan.plan_serving` (which inverts the WSMC requirement model:
`predictor.serving_capacity` turns the per-device HBM budget into a
maximum concurrent-sequence count, and the engine's slot pool is sized
from it; everything beyond queues). Planning defaults to the compile-free
simulator, so the only compiles in a run are the prefill/decode steps that
actually serve traffic; `--backend compile` classifies and verifies with
real compiles instead (honored on the `--mesh auto` path too).

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --reduced \
      --requests 8 --prompt-lens 4,8 --gen-lens 2,4,8 [--mesh auto] \
      [--backend simulate|compile] [--policy continuous|static|both]

On a TPU the default HBM budget is the chip's own (hw.DEVICES, keyed by
device kind) and attention runs the compiled Pallas kernels; `--depth N`
cuts a published-width model to N unit repeats so it fits one chip.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Optional

import jax

from repro import hw as HW
from repro.configs import get_config
from repro.configs.base import DECODE, ShapeConfig, depth_variant
from repro.core import measure as MM
from repro.core.predictor import MemoryPlan
from repro.launch.compile_cache import setup_compile_cache
from repro.models import init_params
from repro.parallel import sharding as SH
from repro.parallel.axes import axis_rules
from repro.search import execplan as XP
from repro.search import space as SP
from repro.serving import (AUDIT_MODES, BlockAllocator, ChaosAllocator,
                           ChaosExecutor, Engine, FaultPlan, LadderConfig,
                           OnlineLengthStats, describe_trace, leak_check,
                           length_stats, survivor_mismatches,
                           synthetic_trace, trace_context)
from repro.serving.executor import JaxExecutor, PagedJaxExecutor


def _int_list(s: str):
    return tuple(int(v) for v in s.split(",") if v)


def main(argv=None, out: Optional[dict] = None):
    """Serve the trace; returns the exit code. A caller that passes an
    `out` dict gets the plan's per-device budget and Eq. 11 requirement
    ceiling and the engine reports filled into it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--depth", type=int, default=0,
                    help="override depth to N unit repeats at the "
                         "config's published widths (fits a model to "
                         "fewer chips; 0 = published depth)")
    # trace knobs (deterministic: same seed + knobs => same trace)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", type=_int_list, default=(4, 8))
    ap.add_argument("--gen-lens", type=_int_list, default=(2, 4, 8))
    ap.add_argument("--arrival-mean", type=float, default=1.0,
                    help="mean inter-arrival ticks; <=0 = burst at tick 0")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context", type=int, default=0,
                    help="ring-cache extent; 0 = max prompt+gen in the trace")
    # planning knobs
    ap.add_argument("--mesh", default="", choices=["", "auto"],
                    help="'' = (data, model) host mesh from --model-parallel; "
                         "'auto' = search the serving lattice for the mesh "
                         "that maximizes admitted concurrency")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--backend", default="simulate",
                    choices=["simulate", "compile"],
                    help="measurement backend for workload classification; "
                         "simulate = zero throwaway compiles at startup")
    ap.add_argument("--hbm-budget-gb", type=float, default=0.0,
                    help="per-device HBM budget for admission; 0 = the "
                         "HBM of the chip the run is on (v5e when planning "
                         "on the CPU)")
    ap.add_argument("--kv", default="ring", choices=["ring", "paged"],
                    help="KV pool layout: 'ring' = worst-case whole-"
                         "sequence slots (baseline); 'paged' = block pool "
                         "with per-sequence block tables — admission "
                         "charges actual footprint, so short requests "
                         "stop paying max-context bytes")
    ap.add_argument("--kv-block", type=int, default=0,
                    help="paged KV block size in positions; 0 = search "
                         "the serving lattice for it")
    ap.add_argument("--compact", action="store_true",
                    help="paged only: compile the decode step at bucketed "
                         "lane widths and pack active lanes into the "
                         "smallest covering bucket each tick — partially "
                         "occupied ticks stop paying full pool width")
    ap.add_argument("--chunk-prefill", type=int, default=0,
                    help="paged only: split prompts longer than this into "
                         "chunks of this many positions, interleaved with "
                         "decode ticks (rounded up to a kv-block multiple; "
                         "0 = whole-prompt prefill at admission)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="paged only: cap on prompt tokens prefilled per "
                         "tick across all mid-prefill lanes, fair-shared "
                         "over SLO classes (needs --chunk-prefill; 0 = "
                         "every pending lane advances one chunk per tick). "
                         "The planner charges the budget — not the whole "
                         "prompt — as the prefill transient, so a tight "
                         "budget converts transient headroom into lanes")
    ap.add_argument("--prefill-kernel", default="tiled",
                    choices=["tiled", "dense"],
                    help="prefill transient model for planning: 'tiled' = "
                         "the fused flash-prefill kernel (O(chunk x block) "
                         "tiles, no score matrix or dequantized fp context "
                         "in HBM); 'dense' = the jnp oracle path that "
                         "materializes O(chunk x context) scores")
    ap.add_argument("--admission", default="worst",
                    choices=["worst", "optimistic"],
                    help="paged only: block reservation discipline. "
                         "'worst' reserves every block a request can "
                         "write (deadlock-free; nothing is preempted); "
                         "'optimistic' reserves E[blocks] + sigma-k "
                         "margin from the trace's length stats and "
                         "evicts-and-requeues (SLO class, then lowest "
                         "progress) when the prediction misses")
    ap.add_argument("--sigma-k", type=float, default=1.0,
                    help="safety margin in per-bucket std deviations for "
                         "--admission optimistic reservations")
    ap.add_argument("--prefix-share", action="store_true",
                    help="paged only: refcount-share the physical blocks "
                         "of the common system-prompt prefix across "
                         "requests (one prefill per unique prefix); "
                         "needs --prefix-len and chunked prefill "
                         "(defaults --chunk-prefill to one kv block)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared system-prompt tokens prepended to every "
                         "request's own prompt (0 = no shared prefix)")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8", "int4"],
                    help="paged only: per-block KV quantization. Blocks "
                         "store int8 (or nibble-packed int4) codes plus "
                         "per-position absmax scales; the decode kernel "
                         "dequantizes on the block-table DMA path, so "
                         "the fp pool is never materialized")
    ap.add_argument("--kv-retain", type=int, default=0,
                    help="paged only: keep only the k most-attended "
                         "blocks per sequence (plus the write tail), "
                         "evicting cold blocks back to the allocator "
                         "free list after each decode tick (0 = exact, "
                         "keep everything)")
    ap.add_argument("--min-agreement", type=float, default=0.0,
                    help="planner floor on predicted token agreement: "
                         "bending candidates (quantized/retained) whose "
                         "agreement prior falls below this are dropped "
                         "before capacity scoring")
    ap.add_argument("--measure-agreement", action="store_true",
                    help="after serving, replay every request through "
                         "exact greedy_generate and report the measured "
                         "token-agreement fraction (slow: one reference "
                         "decode per unique prompt)")
    ap.add_argument("--slo", type=_int_list, default=(0,),
                    help="SLO classes requests draw from (0 = strictest, "
                         "evicted last under pool pressure)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="cap on the engine's slot pool / decode lanes "
                         "(the WSMC capacity is the bound; this caps it "
                         "for small hosts)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="paged only: arm the deterministic chaos harness "
                         "with this seed — transient executor/allocator "
                         "faults, one mid-run 25%% pool shrink, request "
                         "cancellations and a lane stall, all replayed "
                         "identically per seed. After the run the driver "
                         "leak-checks the allocator ledger and replays "
                         "the trace fault-free to prove every surviving "
                         "completion is token-identical")
    ap.add_argument("--deadline", type=int, default=0,
                    help="per-request deadline in ticks from arrival; "
                         "requests still unfinished are cancelled cleanly "
                         "(blocks freed, cause-tagged in the report). "
                         "0 = no deadline")
    ap.add_argument("--audit", default="off", choices=list(AUDIT_MODES),
                    help="paged only: every-tick allocator ledger audit. "
                         "'strict' fails the run on the first corrupt "
                         "tick, 'count' tallies violations into the "
                         "report, 'off' skips the sweep")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "static", "both"])
    ap.add_argument("--forbid-plan-compiles", action="store_true",
                    help="fail if planning attempts an XLA compile (CI "
                         "guard; incompatible with --backend compile)")
    ap.add_argument("--trace-dir", default="",
                    help="write a profiler trace of the serving runs "
                         "here (jax.profiler.trace): the engine's and the "
                         "paged executor's host spans on the device's "
                         "timeline")
    args = ap.parse_args(argv)

    if args.forbid_plan_compiles and args.backend == "compile":
        ap.error("--forbid-plan-compiles contradicts --backend compile")
    if args.kv != "paged" and (args.compact or args.chunk_prefill):
        ap.error("--compact/--chunk-prefill need --kv paged (the ring "
                 "executor has no lane buckets or block tables)")
    if args.kv != "paged" and (args.admission != "worst"
                               or args.prefix_share):
        ap.error("--admission optimistic/--prefix-share need --kv paged "
                 "(the reservation ledger lives on the BlockAllocator)")
    if args.prefix_share and not args.prefix_len:
        ap.error("--prefix-share needs --prefix-len > 0 (there is no "
                 "shared prefix to share otherwise)")
    if args.prefill_budget < 0:
        ap.error("--prefill-budget must be >= 0")
    if args.prefill_budget and not (args.kv == "paged"
                                    and args.chunk_prefill):
        ap.error("--prefill-budget needs --kv paged and --chunk-prefill "
                 "(the budget schedules prompt chunks over block tables; "
                 "whole-prompt prefill is all-or-nothing)")
    if args.kv != "paged" and (args.kv_quant != "none" or args.kv_retain):
        ap.error("--kv-quant/--kv-retain need --kv paged (quantized "
                 "codes and retention both live on the block pool)")
    if args.kv_retain < 0:
        ap.error("--kv-retain must be >= 0")
    if args.kv != "paged" and args.chaos_seed is not None:
        ap.error("--chaos-seed needs --kv paged (pool shrinks and "
                 "allocation faults inject into the block ledger)")
    if args.kv != "paged" and args.audit != "off":
        ap.error("--audit needs --kv paged (the audit sweeps the "
                 "BlockAllocator ledger)")
    if args.deadline < 0:
        ap.error("--deadline must be >= 0")
    if args.depth < 0:
        ap.error("--depth must be >= 0")

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.depth:
        cfg = depth_variant(cfg, args.depth)
    trace = synthetic_trace(args.requests, vocab_size=cfg.vocab_size,
                            seed=args.seed, prompt_lens=args.prompt_lens,
                            gen_lens=args.gen_lens,
                            mean_interarrival=args.arrival_mean,
                            prefix_len=args.prefix_len,
                            slo_classes=args.slo)
    context = args.context or trace_context(trace)
    devices = jax.devices()
    # the chip's own table entry; CPU planning targets v5e explicitly
    hw = (HW.for_device_kind(devices[0].device_kind)
          if devices[0].platform == "tpu" else HW.TPU_V5E)
    shape = ShapeConfig("serve_trace", DECODE, context,
                        max(args.max_slots, 1))
    budget = (args.hbm_budget_gb * 2**30) if args.hbm_budget_gb else None

    # -- plan: mesh + kv sharding + admission bound -------------------------
    # The compile guard is scoped to planning only (restored after), so a
    # later call in the same process can still compile legitimately.
    guard = None
    if args.forbid_plan_compiles:
        from repro.launch import compile as LC

        def _forbidden(*a, **k):
            raise AssertionError(
                "throwaway XLA compile during serve planning "
                "(--forbid-plan-compiles)")
        guard, LC.build = (LC, LC.build), _forbidden
    kv_blocks = ((args.kv_block,) if args.kv_block
                 else tuple(b for b in XP.DEFAULT_KV_BLOCKS if b <= context)
                 or (context,))
    paged_kw = {}
    if args.kv == "paged":
        # the planner maximizes EXPECTED admitted concurrency under the
        # trace's own length distribution (written positions per request)
        # the pool is always sized expected-case (plan_serving default);
        # optimistic admission additionally reserves a sigma-k margin, so
        # the planner carries the same margin into the pool size
        paged_kw = dict(kv="paged", kv_blocks=kv_blocks,
                        seq_lens=[len(r.prompt) + r.max_new - 1
                                  for r in trace],
                        compact=args.compact,
                        sigma_k=(args.sigma_k
                                 if args.admission == "optimistic" else 0.0),
                        kv_quants=(args.kv_quant,),
                        kv_retains=(args.kv_retain,),
                        min_agreement=args.min_agreement,
                        prefill_budget=args.prefill_budget,
                        prefill_kernel=args.prefill_kernel,
                        chunk=args.chunk_prefill)
    try:
        if args.mesh == "auto":
            measurer = None
            if args.backend == "compile":
                from repro.launch.mesh import build_mesh
                measurer = MM.CompileMeasurer(
                    build_mesh({"data": len(devices)}, devices))
            cls, splan = XP.plan_serving(cfg, shape, n_devices=len(devices),
                                         hbm_budget=budget, hw=hw,
                                         measurer=measurer, **paged_kw)
        else:
            host = XP.host_execution(cfg, shape, MemoryPlan(),
                                     len(devices), args.model_parallel)
            if args.backend == "compile":
                measurer = MM.CompileMeasurer(host.build(devices)[0])
            else:
                measurer = MM.SimulatedMeasurer(host.mesh_shape)
            pinned = SP.serving_space(
                cfg, shape, max_devices=len(devices),
                data=(host.mesh_shape.get("data", 1),),
                model=(host.mesh_shape.get("model", 1),),
                kv_blocks=kv_blocks if args.kv == "paged" else (0,),
                kv_quants=((args.kv_quant,) if args.kv == "paged"
                           else ("none",)),
                kv_retains=((args.kv_retain,) if args.kv == "paged"
                            else (0,)))
            cls, splan = XP.plan_serving(cfg, shape, n_devices=len(devices),
                                         hbm_budget=budget, hw=hw,
                                         measurer=measurer, space=pinned,
                                         **paged_kw)
    finally:
        if guard is not None:
            guard[0].build = guard[1]
    print(f"WSMC[serving/{args.backend}]: {cls.category.value} -> "
          f"{splan.describe()}")
    print("trace:", describe_trace(trace))

    n_slots = splan.slots(cap=min(args.max_slots, len(trace)))
    if n_slots < 1:
        print("no serving capacity under the budget; nothing admitted")
        return 1
    n_blocks = splan.pool_blocks(n_slots, context)
    mesh, strategy = splan.execution.build(devices)

    # -- chaos plan ---------------------------------------------------------
    chaos = args.chaos_seed is not None
    plan = None
    if chaos:
        # place shrinks inside the run: rough tick horizon = arrival span
        # plus serial work over the lane count
        work = sum(len(r.prompt) + r.max_new for r in trace)
        horizon = max(64, max(r.arrival for r in trace)
                      + work // max(n_slots, 1))
        plan = FaultPlan.generate(args.chaos_seed, ticks=horizon,
                                  n_requests=len(trace), n_lanes=n_slots,
                                  n_cancels=max(1, len(trace) // 8),
                                  n_stalls=1)
        print("chaos:", plan.describe())

    # -- serve --------------------------------------------------------------
    # parameters are drawn straight into the plan's shardings
    key = jax.random.PRNGKey(args.seed)
    abstract = jax.eval_shape(lambda k: init_params(k, cfg), key)
    params = init_params(key, cfg, SH.to_named(
        mesh, SH.param_specs(cfg, abstract, strategy, mesh)))
    # Eq. 11: capacity = requirement * 4/3 + reserve <= budget, so the
    # plan promises each device at most this requirement
    promised = (splan.hbm_budget - hw.reserved_bytes) / HW.CAPACITY_HEADROOM
    print(f"plan: per-device budget={splan.hbm_budget:.0f} B, Eq.11 "
          f"requirement <= {promised:.0f} B; lanes={n_slots} "
          f"pool_blocks={n_blocks} kv_block={splan.kv_block}")
    if out is not None:
        out.update(budget_bytes=splan.hbm_budget, promised_bytes=promised,
                   reports=[])
    policies = (["continuous", "static"] if args.policy == "both"
                else [args.policy])
    reports = []
    failures = []
    profile = (jax.profiler.trace(args.trace_dir) if args.trace_dir
               else contextlib.nullcontext())
    with profile, mesh, axis_rules(strategy.rules(), mesh=mesh):
        for policy in policies:
            chunk = 0
            if args.kv == "paged":
                if args.chunk_prefill:       # align up to the block size
                    chunk = -(-args.chunk_prefill // splan.kv_block) \
                        * splan.kv_block
                elif args.prefix_share:      # suffixes ride the chunked path
                    chunk = splan.kv_block
                executor = PagedJaxExecutor(
                    params, cfg, n_lanes=n_slots, n_blocks=n_blocks,
                    kv_block=splan.kv_block, context=context,
                    compact=args.compact, chunk=chunk,
                    kv_quant=args.kv_quant, kv_retain=args.kv_retain)
                reservation = ("expected"
                               if args.admission == "optimistic"
                               else "worst")
                if chaos:
                    allocator = ChaosAllocator(n_blocks, splan.kv_block,
                                               reservation, plan=plan)
                else:
                    allocator = BlockAllocator(n_blocks, splan.kv_block,
                                               reservation=reservation)
            else:
                executor = JaxExecutor(params, cfg, n_slots=n_slots,
                                       context=context)
                allocator = None

            def mk_stats():
                # EW-updated online stats: reservations track the live
                # length distribution, and the report carries observed
                # sigma_k per prompt bucket
                if args.admission != "optimistic":
                    return None
                return OnlineLengthStats(base=length_stats(trace))
            run_exec = ChaosExecutor(executor, plan) if chaos else executor
            engine = Engine(run_exec, n_slots, policy=policy,
                            allocator=allocator, chunk_prefill=chunk,
                            prefill_budget=args.prefill_budget,
                            prefix_share=args.prefix_share,
                            stats=mk_stats(), sigma_k=args.sigma_k,
                            kv_retain=(args.kv_retain
                                       if args.kv == "paged" else 0),
                            deadline=args.deadline, faults=plan,
                            ladder=(LadderConfig() if chaos else None),
                            audit=args.audit)
            t0 = time.time()
            report = engine.run(trace)
            dt = time.time() - t0
            lp = report.latency_percentiles()
            tp = report.ttft_percentiles()
            print(report.describe() + f" wall={dt:.2f}s "
                  f"compiles={executor.compile_counts()}")
            if out is not None:
                out["reports"].append(report)
            if lp and tp:  # both empty when nothing completed
                print(f"  latency p50/p95/p99={lp['p50']:.0f}/"
                      f"{lp['p95']:.0f}/{lp['p99']:.0f} ticks "
                      f"ttft p50/p95/p99={tp['p50']:.0f}/{tp['p95']:.0f}/"
                      f"{tp['p99']:.0f} mean_ttft={report.mean_ttft():.1f} "
                      f"evictions={report.evictions}")
            if args.measure_agreement:
                from repro.serving.quality import token_agreement
                agree = token_agreement(params, cfg, trace, report,
                                        context=context)
                print(f"  {agree.describe()}")
            if chaos:
                # prove the harness didn't corrupt anything: the drained
                # ledger must be whole, and every request the chaos run
                # completed must be token-identical to a fault-free
                # replay (same executor, reset pool, clean allocator)
                problems = leak_check(allocator)
                executor.reset()
                clean = Engine(
                    executor, n_slots, policy=policy,
                    allocator=BlockAllocator(n_blocks, splan.kv_block,
                                             reservation=reservation),
                    chunk_prefill=chunk,
                    prefill_budget=args.prefill_budget,
                    prefix_share=args.prefix_share,
                    stats=mk_stats(), sigma_k=args.sigma_k,
                    kv_retain=(args.kv_retain
                               if args.kv == "paged" else 0)).run(trace)
                problems += survivor_mismatches(report, clean)
                if problems:
                    for p in problems:
                        print(f"  CHAOS FAILURE: {p}")
                    failures.extend(problems)
                else:
                    print(f"  chaos: ledger clean, "
                          f"{len(report.completions)} survivors "
                          f"token-identical to fault-free replay")
            reports.append(report)

    if args.policy == "both" and len(reports) == 2:
        cont, stat = reports
        print(f"occupancy: continuous={cont.occupancy():.3f} vs "
              f"static={stat.occupancy():.3f} "
              f"(+{(cont.occupancy() - stat.occupancy()) * 100:.1f} pts)")
    if failures:
        print(f"ERROR: {len(failures)} chaos check(s) failed")
        return 1
    if chaos or args.deadline:
        # faults and deadlines may legitimately cancel requests; every
        # request must still be ACCOUNTED for — completed or cause-tagged
        done = min(len(r.completions) + len(r.cancellations)
                   for r in reports)
        if done != len(trace):
            print(f"ERROR: {done}/{len(trace)} requests accounted for")
            return 1
        return 0
    completed = min(len(r.completions) for r in reports)
    if completed != len(trace):
        print(f"ERROR: {completed}/{len(trace)} requests completed")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
