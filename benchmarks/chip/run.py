#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name: the cell in BENCHMARK.json, its configuration
file, `traffic/<mix>.json`, `references/<family>.py` and
`adapters/<family>.py` for the configuration's model family, and
`metrics/<metric>.py` for each per-layer metric. No code here knows a cell.

A run builds the system under test through its normal path (see
harness/serve.py), warms every shape the cell's traffic can reach (set-up),
ramps the load, measures for --seconds, then checks a sample of the served
tokens against the float32 reference (harness/check.py). With --trace 0 the
result line carries the cell's end-to-end metrics; with --trace 1 a
profiler trace of the middle of the window gives its per-layer metrics.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device[, breakdown], compared. A run that finds no TPU, or
fewer chips than the cell asks for, exits 1 and prints no result.

--rehearse runs the same control flow on the CPU at the configuration's
`rehearsal` sizes, on as many virtual CPU devices as the cell has chips,
and prints no device metric.

The persistent compilation cache is `<checkout>/.bench_jax_cache`, so only
a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import importlib                                             # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import tempfile                                              # noqa: E402
import traceback                                             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".bench_jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _applies(metric, cell) -> bool:
    return cell["name"] in metric.get("workloads", [cell["name"]])


def load_cell(name: str, rehearse: bool):
    """The cell's BENCHMARK.json entries and data files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        config = {**config, **config["rehearsal"],
                  "serving": {**config["serving"],
                              **config["rehearsal"]["serving"]}}
        traffic = {**traffic, **traffic["rehearsal"]}
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    layer = [m for m in bench["per_layer"] if _applies(m, cell)]
    return cell, config, traffic, e2e, layer


def setup_jax(cpu_devices: int = 1):
    """Import JAX with the persistent compilation cache at the checkout's
    fixed directory (off on the CPU), and the program and the harness on
    the path. `cpu_devices` > 1 gives the CPU backend that many devices (a
    rehearsal of a cell on several chips)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    if cpu_devices > 1:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={cpu_devices}"]))
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import jax
    if jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    return jax


def end_to_end(d, seconds: float, names) -> dict:
    """The user's numbers, from the host clock: nearest-rank percentiles
    of every gap between two tokens of a request whose later token reached
    the host in the window, and of the time to first token of every
    request due in the window; tokens that reached the host in the window
    over its seconds."""
    from harness.stats import percentile
    ws, we = d.window
    itl = [b - a for r in d.reqs.values()
           for a, b in zip(r.stamps, r.stamps[1:]) if ws <= b < we]
    ttft = [r.stamps[0] - r.due for r in d.reqs.values()
            if ws <= r.due < we and r.stamps]
    toks = sum(1 for r in d.reqs.values() for t in r.stamps if ws <= t < we)
    vals = {}
    if itl:
        vals["itl_p50_ms"] = 1e3 * percentile(itl, 50)
        vals["itl_p95_ms"] = 1e3 * percentile(itl, 95)
    if ttft:
        vals["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    vals["output_tok_s"] = toks / seconds
    return {k: vals[k] for k in names if k in vals}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes, no device metric")
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1: copy the profiler trace here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    cell, config, tspec, e2e, layer = load_cell(args.workload,
                                                args.rehearse)
    chips = cell["chips"]
    jax = setup_jax(chips if args.rehearse else 1)
    from harness import check, costs, metrics, serve
    from harness import trace as T
    from harness.traffic import Traffic

    devices = jax.devices()
    peaks = None
    if not args.rehearse:
        if devices[0].platform != "tpu":
            log(f"run.py: needs a TPU, JAX found {devices[0].platform!r}")
            return 1
        peaks = costs.peaks(devices[0].device_kind)
    if len(devices) < chips:
        log(f"run.py: the cell needs {chips} chips, JAX sees "
            f"{len(devices)}")
        return 1
    used = devices[:chips]
    ref = importlib.import_module(f"references.{config['family']}")
    adapter = importlib.import_module(f"adapters.{config['family']}")

    clock = serve.CompileClock()
    t_imported = time.perf_counter()
    traffic = Traffic(tspec, args.seed, config["vocab_size"])
    sysm = serve.build(config, traffic, args.seed, used, adapter)
    n_warm = serve.warm_up(sysm, traffic)
    setup_s = time.perf_counter() - T_START
    log(f"setup: lanes={sysm.n_lanes} blocks={sysm.n_blocks} "
        f"kv_block={sysm.kv_block} chunk={sysm.chunk} "
        f"context={sysm.context} warm_calls={n_warm} "
        f"compiles={clock.count} compile_s={clock.seconds:.1f} "
        f"setup_s={setup_s:.1f}")
    log("setup phases: start=%.1f " % (t_imported - T_START) + " ".join(
        f"{k}={v:.1f}" for k, v in sysm.phases.items()))
    log(f"setup events: {clock.summary()}")

    tmp = tempfile.TemporaryDirectory() if args.trace else None
    t_drive = time.perf_counter()
    d = serve.drive(sysm, traffic, args.seconds, clock,
                    trace_dir=tmp.name if tmp else None)
    t_drained = time.perf_counter()
    print(f"compiles_in_window={d.compiles_in_window}", flush=True)
    late = sorted(d.loadgen_late_s)
    log(f"window: requests offered={len(d.reqs)} ticks={len(d.ticks)} "
        f"load generator late p99="
        f"{1e3 * late[int(0.99 * (len(late) - 1))] if late else 0:.2f} ms")
    peak = (max(dv.memory_stats()["peak_bytes_in_use"] for dv in used)
            if not args.rehearse else None)

    ws, we = d.window
    finished = {rid: (r.prompt, r.tokens) for rid, r in d.reqs.items()
                if r.tokens is not None}
    due = [r for r in d.reqs.values() if ws <= r.due < we]
    # an open-loop request with no token by the end of the drain failed; a
    # closed loop's requests still queued at the close are its backlog
    attempted = len(due)
    failed = sum(1 for r in due if not r.stamps) if traffic.open else 0
    ticks = [t for t in d.ticks if ws <= t.t0 and t.t1 <= we]
    traced_calls = [c for c in sysm.recorder.calls if c.traced]
    promised, kv_block = sysm.promised_bytes, sysm.kv_block
    chunk, n_lanes = sysm.chunk, sysm.n_lanes
    sysm.stack.close()
    del sysm
    gc.collect()

    picked = check.sample(finished, args.seed, chunk)
    t_ref = time.perf_counter()
    gaps = check.served_gaps(ref, config, args.seed,
                             [finished[r][0] for r in picked],
                             [finished[r][1] for r in picked])["gap"]
    gap = check.widest(gaps)
    limit = config["correct"]["logit_gap_limit"]
    correct = check.judge(gap, limit)
    t_checked = time.perf_counter()
    log(f"reference: {len(picked)} requests, {len(gaps)} served tokens, "
        f"{t_checked - t_ref:.1f} s")
    log(f"run phases: setup={setup_s:.1f} ramp={ws - t_drive:.1f} "
        f"window={we - ws:.1f} drain={t_drained - we:.1f} "
        f"free={t_ref - t_drained:.1f} reference={t_checked - t_ref:.1f}")

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        xplane = T.find_xplane(tmp.name)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xplane, args.keep_trace)
        tr = T.load(xplane)
        tmp.cleanup()
    if args.rehearse:
        result["metrics"] = {}
    elif args.trace:
        ctx = metrics.Context(
            config=config, ref=ref, devices=[dv.id for dv in used],
            peaks=peaks, trace=tr,
            calls=traced_calls, ticks=ticks, counters=d.counters,
            memory_peak_bytes=peak, promised_bytes=promised,
            kv_block=kv_block)
        vals = metrics.read_all([m["name"] for m in layer], ctx)
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in layer if m["name"] in vals}
        device.update(busy_s=T.mean_busy_s(tr, ctx.devices),
                      window_s=tr.window_s)
        result["breakdown"] = {"device_ops": T.top_ops(tr, ctx.devices[0]),
                               "idle_gaps": T.idle_gaps(tr, ctx.devices[0])}
    else:
        vals = end_to_end(d, args.seconds,
                          [m["name"] for m in e2e if m["name"] != "setup_s"])
        vals["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in e2e if m["name"] in vals}
    result["device"] = device
    result["compared"] = {"logit_gap_max": {"value": gap, "limit": limit}}
    log(f"lanes={n_lanes} finished={len(finished)} checked={picked}")
    log(f"compared: logit_gap_max={gap!r} limit={limit!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        sys.exit(1)
