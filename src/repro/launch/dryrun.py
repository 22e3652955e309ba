import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init). Everything below is ordinary.
"""Multi-pod dry-run driver.

For every (architecture × input shape) cell:
  1. offline/online WSMC phases pick the memory plan (knowledge base),
  2. the full-depth step is measured on the single-pod (16,16) mesh AND
     the multi-pod (2,16,16) mesh — under the default compile backend,
     memory_analysis() proves the per-device footprint and the multi-pod
     pass proves the "pod" axis shards; under --backend simulate the same
     sweep runs compile-free in seconds via the analytical measurer,
  3. depth-1/2 unrolled variants provide scan-corrected roofline terms
     (single-pod, compile backend only — §Roofline).

Artifacts: one JSON per cell under --out, plus a summary table.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out artifacts/dryrun [--no-roofline] [--kb artifacts/kb.json] \
      [--backend compile|simulate] [--profile-cache artifacts/profiles.json]
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict

from repro.configs import (ARCH_IDS, SHAPES, SHAPE_ORDER, get_config,
                           shape_applicable)
from repro.configs.base import ShapeConfig, depth_variant  # noqa: F401 — depth_variant re-exported for back-compat
from repro.core import measure as MM
from repro.core import profiler as PF
from repro.core.classifier import Classification, Category
from repro.launch import compile as LC
from repro.launch.mesh import make_production_mesh
from repro.models.model import ModelSettings
from repro.roofline import analysis as RA
from repro.search import execplan as XP
from repro.search import strategies as ST

# Mesh shapes the driver sweeps; under --backend simulate no jax Mesh (and
# no fake-device process) is ever constructed — the dicts are enough.
MESH_SHAPES = {
    "single": {"data": 16, "model": 16},
    "multi": {"pod": 2, "data": 16, "model": 16},
}


def classification_for(cfg, shape, measurer: MM.MemoryMeasurer,
                       kb: Dict) -> Classification:
    key = f"{cfg.name}::{shape.kind}"
    if key in kb:
        e = kb[key]
        return Classification(category=Category(e["category"]),
                              alpha=e["alpha"], inc=e["inc"],
                              slope=e["slope"], intercept=e["intercept"])
    cls = PF.classify_workload(cfg, shape, None, n_points=3, base_seq=512,
                               measurer=measurer)
    kb[key] = {"category": cls.category.value, "alpha": cls.alpha,
               "inc": cls.inc, "slope": cls.slope,
               "intercept": cls.intercept, "factor": cls.factor}
    return cls


def paper_faithful_settings(scan_layers: bool = True) -> ModelSettings:
    """Disable the beyond-paper defaults (EXPERIMENTS §Perf) for baseline
    cells: replicated GQA sharding + gather embedding."""
    from repro.models.attention import AttnSettings
    return ModelSettings(scan_layers=scan_layers, embed_onehot=False,
                         attn=AttnSettings(repeat_kv=False))


def run_cell(arch: str, shape: ShapeConfig,
             measurers: Dict[str, MM.MemoryMeasurer],
             kb: Dict, do_roofline: bool = True,
             plan_override=None, settings_fn=ModelSettings,
             strategy: str = "fastest", *, auto_mesh: bool = False,
             backend: str = "simulate", cache=None,
             max_devices: int = 256) -> dict:
    cfg = get_config(arch)
    result = {"arch": arch, "shape": shape.name, "kind": shape.kind}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        result["status"] = "skipped"
        result["reason"] = reason
        return result

    # The single-pod measurer anchors profiling/roofline; a multi-only
    # sweep (--mesh multi) profiles on the multi-pod mesh instead.
    single_m = measurers.get("single") or next(iter(measurers.values()))
    result["backend"] = backend if auto_mesh else single_m.backend
    # --- WSMC online phase (profiling ladder on the single-pod mesh) ----
    t0 = time.time()
    cls = classification_for(cfg, shape, single_m, kb)
    plan = plan_override
    if auto_mesh:
        # plan the mesh, then build it: the measurement target IS the
        # planned mesh (pipe included), not a CLI-fixed one
        sim = (single_m if single_m.backend == "simulate"
               else MM.SimulatedMeasurer(single_m.mesh_shape))
        if backend == "compile":
            # the planned mesh must be buildable on this host's (fake)
            # devices, not just within the abstract budget
            import jax
            max_devices = min(max_devices, len(jax.devices()))
        eplan = XP.plan_execution(cfg, shape, cls, n_devices=max_devices,
                                  strategy=strategy, measurer=sim,
                                  factors=PF.calibrated_factors(kb))
        plan = eplan.plan
        result["execution_plan"] = {
            "mesh": eplan.mesh_shape, "schedule": eplan.schedule,
            "ep": eplan.ep, "plan": dataclasses.asdict(eplan.plan),
            "policy": eplan.policy, "n_devices": eplan.n_devices,
            "strategy": strategy,
        }
        print(f"[{arch} × {shape.name}] planned: {eplan.describe()}",
              flush=True)
        if backend == "simulate":
            planned_m = MM.SimulatedMeasurer(eplan.mesh_shape, cache=cache,
                                             ep=eplan.ep)
        else:
            mesh, _ = eplan.build()
            planned_m = MM.CompileMeasurer(mesh, cache=cache)
        measurers = {"planned": planned_m}
    elif plan is None:
        factors = PF.calibrated_factors(kb)
        decision = ST.plan_for(cfg, shape, cls, single_m.mesh_shape,
                               strategy=strategy, measurer=single_m,
                               factors=factors)
        plan = decision.plan
        result["wsmc"] = {
            "category": cls.category.value,
            "alpha": round(cls.alpha, 3),
            "inc": round(cls.inc, 3),
            "plan": dataclasses.asdict(plan),
            "policy": decision.policy,
            "strategy": strategy,
            "considered": decision.considered,
            "measured": decision.measured,
        }
        if decision.prediction is not None:
            result["wsmc"]["pred_capacity_bytes"] = \
                decision.prediction.capacity_bytes
            result["wsmc"]["pred_fits"] = decision.prediction.fits
        if decision.peak_bytes is not None:
            result["wsmc"]["verified_peak_bytes"] = decision.peak_bytes
    result["profile_s"] = round(time.time() - t0, 1)

    # --- full-depth measurement on each mesh ----------------------------
    for mesh_name, measurer in measurers.items():
        t0 = time.time()
        # re-plan per mesh: microbatch divisibility depends on the dp size
        # (auto mode already planned plan + mesh together)
        if plan_override is None and not auto_mesh:
            mesh_plan = ST.plan_for(cfg, shape, cls, measurer.mesh_shape,
                                    strategy=strategy, measurer=measurer,
                                    factors=PF.calibrated_factors(kb)).plan
        else:
            mesh_plan = plan
        st = settings_fn(scan_layers=True)
        prof = measurer.measure(cfg, shape, mesh_plan, settings=st)
        entry = {
            "argument_bytes": int(prof.argument_bytes),
            "output_bytes": int(prof.output_bytes),
            "temp_bytes": int(prof.transient_bytes),
            "peak_static_bytes": int(prof.peak_bytes),
            "measure_s": round(time.time() - t0, 1),
            "n_devices": int(MM.n_devices_of(measurer.mesh_shape)),
            "alpha_full": round(prof.alpha, 3),
        }
        print(f"[{arch} × {shape.name} × {mesh_name}] "
              f"{measurer.backend} measure: args={entry['argument_bytes']} "
              f"temp={entry['temp_bytes']} out={entry['output_bytes']}",
              flush=True)
        if mesh_name == "single" and measurer.last_compiled is not None:
            # raw HLO flops only exist under the compile backend (and only
            # when the profile wasn't served from the cache)
            ca = measurer.last_compiled.cost_analysis()
            print(f"[{arch} × {shape.name} × {mesh_name}] cost_analysis "
                  f"(scan counts body once): flops={ca.get('flops', 0):.3e}",
                  flush=True)
            entry["raw_cost_flops"] = float(ca.get("flops", 0.0))
        measurer.last_compiled = None
        result[f"mesh_{mesh_name}"] = entry

    # --- roofline (depth-extrapolated, single-pod, compile backend) ------
    single = (measurers["single"].mesh
              if "single" in measurers
              and measurers["single"].backend == "compile" else None)
    if do_roofline and single is not None:
        t0 = time.time()
        # microbatches=1: the microbatch loop is a lax.scan whose body
        # cost_analysis counts once; the per-step cost equals the full-batch
        # single-micro cost, so lower that directly.
        rplan = dataclasses.replace(plan, microbatches=1)
        costs = []
        for n_units in (1, 2):
            dcfg = depth_variant(cfg, n_units)
            strategy = PF.strategy_for(dcfg, rplan, single)
            st = settings_fn(scan_layers=False)
            dt = PF._tcfg_for(rplan, settings=st)
            bundle = LC.build(dcfg, shape, single, strategy=strategy,
                              tcfg=dt, settings=st)
            costs.append(RA.component_cost(bundle.compile()))
        total = RA.extrapolate(costs[0], costs[1], cfg.repeats)
        total = RA.apply_corrections(
            total, RA.scan_corrections(cfg, shape, single.devices.size))
        rep = RA.report(cfg, shape, "single", single.devices.size, total,
                        remat=rplan.remat)
        result["roofline"] = rep.to_dict()
        result["roofline"]["analysis_s"] = round(time.time() - t0, 1)

    result["status"] = "ok"
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both", "auto"],
                    help="'auto' = plan the mesh per cell (mesh_space "
                         "search) and measure on the planned mesh")
    ap.add_argument("--max-devices", type=int, default=256,
                    help="device budget for --mesh auto planning")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--kb", default="artifacts/kb.json")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--paper-faithful", action="store_true",
                    help="disable the beyond-paper default optimizations "
                         "(baseline reproduction cells)")
    ap.add_argument("--backend", default="compile",
                    choices=["compile", "simulate"],
                    help="memory-measurement backend: 'compile' = XLA "
                         "memory_analysis() ground truth (slow), 'simulate' "
                         "= closed-form analytical model (zero compiles)")
    ap.add_argument("--strategy", default="fastest",
                    choices=list(ST.CLI_STRATEGIES),
                    help="plan-search strategy: 'fastest' = the paper's "
                         "predicted walk, 'staged' = simulator-screened "
                         "top-k verified on --backend, 'exhaustive' = "
                         "verify every candidate, 'greedy' = coordinate "
                         "hillclimb")
    ap.add_argument("--profile-cache", default=None,
                    help="path of the on-disk MemoryProfile cache (keyed by "
                         "arch × shape × plan × mesh × backend)")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPE_ORDER) if args.shape == "all" else args.shape.split(",")

    cache = MM.ProfileCache(args.profile_cache) if args.profile_cache else None
    measurers = {}
    if args.mesh == "auto":
        # classification screen: always the compile-free simulator; the
        # measurement mesh is planned per cell inside run_cell
        measurers["screen"] = MM.SimulatedMeasurer(MESH_SHAPES["single"],
                                                   cache=cache)
    for name in ("single", "multi"):
        if args.mesh not in (name, "both"):
            continue
        if args.backend == "compile":
            mesh = make_production_mesh(multi_pod=(name == "multi"))
        else:
            mesh = MESH_SHAPES[name]     # no jax mesh needed to simulate
        measurers[name] = MM.measurer_for(args.backend, mesh, cache=cache)

    os.makedirs(args.out, exist_ok=True)
    kb = {}
    if os.path.exists(args.kb):
        kb = PF.load_knowledge_base(args.kb)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            shape = SHAPES[shape_name]
            cell_path = os.path.join(args.out, f"{arch}__{shape_name}.json")
            if os.path.exists(cell_path):
                with open(cell_path) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[cached] {arch} × {shape_name}:"
                          f" {prev['status']}", flush=True)
                    n_ok += prev["status"] == "ok"
                    n_skip += prev["status"] == "skipped"
                    continue
            t0 = time.time()
            try:
                settings_fn = (paper_faithful_settings if args.paper_faithful
                               else ModelSettings)
                result = run_cell(arch, shape, measurers, kb,
                                  do_roofline=not args.no_roofline,
                                  settings_fn=settings_fn,
                                  strategy=args.strategy,
                                  auto_mesh=args.mesh == "auto",
                                  backend=args.backend, cache=cache,
                                  max_devices=args.max_devices)
            except Exception as e:  # noqa: BLE001 — record and continue
                result = {"arch": arch, "shape": shape_name,
                          "status": "failed", "error": str(e),
                          "traceback": traceback.format_exc()}
            result["total_s"] = round(time.time() - t0, 1)
            with open(cell_path, "w") as f:
                json.dump(result, f, indent=2)
            PF.save_knowledge_base(args.kb, kb)
            st = result["status"]
            n_ok += st == "ok"
            n_skip += st == "skipped"
            n_fail += st == "failed"
            print(f"[{st}] {arch} × {shape_name} ({result['total_s']}s)",
                  flush=True)
            if st == "failed":
                print(result["error"], flush=True)

    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_fail} failed",
          flush=True)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
