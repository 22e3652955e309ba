"""End-to-end training driver.

WSMC is in the loop: unless knobs are forced, the driver profiles the
workload on a small-shape ladder, classifies it, and applies the planned
memory configuration before the first real step (paper §III-E online phase).
With `--mesh auto` the mesh itself is a planned output: the driver searches
the runnable mesh_space (data / model / pipe axes), builds the winning mesh
and executes the matching runtime schedule — including the 1F1B pipeline
when the plan says pipe > 1.

Examples:
  # tiny CPU run (reduced config), 50 steps:
  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --reduced --seq 128 --batch 8 --steps 50

  # plan the mesh, then build it (8 fake host devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch h2o-danube-1.8b --reduced \
      --depth 4 --seq 64 --batch 8 --steps 10 --mesh auto

  # force a pipelined mesh (pipe=2 stages x data=2):
  ... --mesh data:2,pipe:2 --microbatches 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.configs import get_config
from repro.configs.base import ShapeConfig, TRAIN, depth_variant
from repro.core import measure as MM
from repro.core import planner as PL
from repro.core import profiler as PF
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import build_mesh
from repro.models import init_params
from repro.optim import optimizers as opt
from repro.parallel.axes import axis_rules
from repro.runtime import fault as F
from repro.runtime import schedule as SCH
from repro.runtime import schedule_kinds as SK
from repro.runtime.train_step import TrainStepConfig
from repro.search import execplan as XP
from repro.search import space as SP
from repro.search import strategies as ST


def reduced_100m(cfg):
    """~100M-parameter variant of an arch family (examples deliverable)."""
    return dataclasses.replace(
        cfg.reduced(), name=cfg.name + "-100m",
        d_model=512, head_dim=64, n_heads=8,
        n_kv_heads=min(8, max(1, cfg.n_kv_heads)),
        d_ff=0 if cfg.d_ff == 0 else 2048, vocab_size=32000,
        lru_width=None if cfg.lru_width is None else 512)


def fit_microbatches(cfg, plan, mesh_shape: dict, batch: int):
    """Clamp the plan's microbatch count to what the mesh can execute: it
    must divide the global batch and — on pipe meshes — satisfy the shared
    1F1B executability predicate (schedule_kinds.pipeline_problems: fill
    the pipeline, per-microbatch batch divisible by the data axes; the
    flat scan schedule needs only batch divisibility). Planned results
    from mesh_space already satisfy this — forced meshes, CLI overrides,
    and the staged/exhaustive paper-space strategies (which skip the
    fastest-first dp filter) may not. Prefers the nearest valid value to
    the planned one."""
    pipe = max(int(mesh_shape.get("pipe", 1)), 1)

    def ok(m):
        if batch % m:
            return False
        if pipe <= 1:
            return True
        return SK.pipeline_executable(cfg, m, mesh_shape, batch)

    m0 = max(plan.microbatches, 1)
    if ok(m0):
        return plan
    fits = [m for m in range(1, batch + 1) if ok(m)]
    if not fits:
        why = "; ".join(SK.pipeline_problems(cfg, m0, mesh_shape, batch))
        raise ValueError(f"global batch {batch} cannot run on mesh "
                         f"{mesh_shape}: {why}")
    micro = min(fits, key=lambda m: (abs(m - m0), m))
    print(f"note: adjusted microbatches {m0} -> {micro} to fit "
          f"pipe={pipe}, batch={batch}")
    return dataclasses.replace(plan, microbatches=micro)


def parse_mesh(spec: str) -> dict:
    """'data:2,pipe:2' -> {'data': 2, 'pipe': 2}. Unknown axis names are
    rejected — a typo ('pip:2') would otherwise train on a silently inert
    axis."""
    from repro.launch.mesh import CANONICAL_AXES
    out = {}
    for part in spec.split(","):
        axis, sep, n = part.partition(":")
        axis = axis.strip()
        if not sep or not n.strip().isdigit() or int(n) < 1:
            raise ValueError(f"bad --mesh entry {part!r}; want axis:size "
                             "with size >= 1")
        if axis not in CANONICAL_AXES:
            raise ValueError(f"unknown mesh axis {axis!r}; "
                             f"known: {CANONICAL_AXES}")
        if axis in out:
            raise ValueError(f"duplicate mesh axis {axis!r} in {spec!r}")
        out[axis] = int(n)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--reduced-100m", action="store_true")
    ap.add_argument("--depth", type=int, default=0,
                    help="override depth to N unit repeats (pipeline stages "
                         "split the repeats: pick a multiple of pipe)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="",
                    help="'' = legacy (data, model) host mesh from "
                         "--model-parallel; 'auto' = search mesh_space and "
                         "build the planned mesh (pipe included); "
                         "'data:2,pipe:2' = forced mesh")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--strategy", default="fastest",
                    choices=list(ST.CLI_STRATEGIES),
                    help="plan-search strategy for the WSMC online phase")
    ap.add_argument("--backend", default="simulate",
                    choices=["simulate", "compile"],
                    help="memory-measurement backend for the profiling "
                         "ladder; simulate = zero planning compiles")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=100)
    ap.add_argument("--remat", default="")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--optimizer", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced_100m:
        cfg = reduced_100m(cfg)
    elif args.reduced:
        cfg = cfg.reduced()
    if args.depth:
        cfg = depth_variant(cfg, args.depth)
    shape = ShapeConfig("train_cli", TRAIN, args.seq, args.batch)
    devices = jax.devices()

    forced_plan = None
    if args.remat and args.microbatches and args.optimizer:
        forced_plan = PL.MemoryPlan(remat=args.remat,
                                    microbatches=args.microbatches,
                                    optimizer=args.optimizer)

    def apply_overrides(plan):
        if args.remat:
            plan = dataclasses.replace(plan, remat=args.remat)
        if args.microbatches:
            plan = dataclasses.replace(plan, microbatches=args.microbatches)
        if args.optimizer:
            plan = dataclasses.replace(plan, optimizer=args.optimizer)
        return plan

    if args.mesh and args.model_parallel != 1:
        print("note: --model-parallel only shapes the legacy host mesh; "
              "with --mesh the model axis comes from the plan/spec")

    # ---- WSMC online phase: plan (and possibly the mesh) ----------------
    if args.mesh == "auto":
        # mesh is a planned OUTPUT: classify compile-free, search the
        # runnable mesh_space, promote the winner to an ExecutionPlan
        if args.backend == "compile":
            print("note: --mesh auto plans with the compile-free simulator; "
                  "--backend compile only affects fixed-mesh planning")
        cls, eplan = XP.auto_plan(cfg, shape, n_devices=len(devices),
                                  strategy=args.strategy,
                                  base_seq=min(64, args.seq))
        plan = fit_microbatches(cfg, apply_overrides(eplan.plan),
                                eplan.mesh_shape, args.batch)
        if plan != eplan.plan:
            eplan = dataclasses.replace(
                eplan, plan=plan,
                schedule=SCH.schedule_kind(TRAIN, plan.microbatches,
                                           eplan.pipe))
        print(f"WSMC[auto]: {cls.category.value} (alpha={cls.alpha:.2f}, "
              f"inc={cls.inc:.2f}) -> {eplan.describe()}")
        mesh, strategy = eplan.build(devices)
    else:
        if args.mesh:
            mesh_shape = parse_mesh(args.mesh)
        else:
            mesh_shape = XP.host_execution(cfg, shape, PL.MemoryPlan(),
                                           len(devices),
                                           args.model_parallel).mesh_shape
        mesh = build_mesh(mesh_shape, devices)
        if forced_plan is not None:
            # the CLI has no kv flag: resolve the cache layout against the
            # mesh's model axis like default_strategy always did
            plan = dataclasses.replace(
                forced_plan,
                kv_shard=SP.kv_auto(cfg, int(mesh_shape.get("model", 1))))
            policy = "forced"
            print(f"plan (forced): {plan}")
        else:
            if args.backend == "simulate":
                measurer = MM.SimulatedMeasurer(mesh_shape)
            else:
                measurer = MM.CompileMeasurer(mesh)
            cls = PF.classify_workload(cfg, shape, mesh, n_points=2,
                                       base_seq=min(64, args.seq),
                                       measurer=measurer)
            res = ST.plan_for(cfg, shape, cls, mesh_shape,
                              strategy=args.strategy, measurer=measurer)
            plan = apply_overrides(res.plan)
            policy = res.policy
            print(f"WSMC[{args.strategy}/{args.backend}]: "
                  f"{cls.category.value} (alpha={cls.alpha:.2f}, "
                  f"inc={cls.inc:.2f}) -> plan {plan} "
                  f"{res.describe_outcome()}")
        plan = fit_microbatches(cfg, plan, mesh_shape, args.batch)
        eplan = XP.for_mesh(cfg, shape, plan, mesh_shape, policy=policy)
        strategy = eplan.strategy()
        print(f"execution: {eplan.describe()}")

    plan = eplan.plan
    tcfg = TrainStepConfig(
        remat=plan.remat, microbatches=plan.microbatches,
        optimizer=opt.OptimizerConfig(kind=plan.optimizer, lr=args.lr),
        warmup_steps=max(args.steps // 10, 1), total_steps=args.steps)

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = opt.init_state(tcfg.optimizer, params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,} mesh={dict(mesh.shape)} "
          f"schedule={eplan.schedule}")

    step_fn = jax.jit(
        SCH.make_train_step(cfg, tcfg, mesh=mesh, schedule=eplan.schedule),
        donate_argnums=(0, 1))
    data_pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                         seq_len=args.seq,
                                         global_batch=args.batch,
                                         seed=args.seed))

    ckpt_mgr = (F.CheckpointManager(args.ckpt_dir, args.ckpt_interval)
                if args.ckpt_dir else None)
    start_step = 0
    if ckpt_mgr and ckpt_mgr.latest_step() is not None:
        tree = {"params": params, "opt": opt_state}
        tree, manifest = ckpt_mgr.restore(tree)
        params, opt_state = tree["params"], tree["opt"]
        start_step = manifest["extra"].get("step", manifest["step"])
        print(f"resumed from step {start_step}")

    guard = F.PreemptionGuard(install=True)
    watchdog = F.Watchdog()

    def on_metrics(step, m):
        if step % args.log_every == 0:
            print(f"step {step:5d} loss={m['loss']:.4f} "
                  f"gnorm={m['grad_norm']:.2f} lr={m['lr']:.2e}", flush=True)

    with mesh, axis_rules(strategy.rules(), mesh=mesh):
        t0 = time.time()
        params, opt_state, last, hist = F.run_train_loop(
            train_step=step_fn, params=params, opt_state=opt_state,
            pipeline=data_pipe, n_steps=args.steps, ckpt_mgr=ckpt_mgr,
            watchdog=watchdog, guard=guard, start_step=start_step,
            on_metrics=on_metrics)
        dt = time.time() - t0
    if hist:
        print(f"done: {last - start_step} steps in {dt:.1f}s "
              f"({dt / max(last - start_step, 1):.2f}s/step), "
              f"final loss {hist[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
