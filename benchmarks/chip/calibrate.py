#!/usr/bin/env python3
"""Readings that set the limit of `correct`, for one cell, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 \
        --control 3 --seconds 20 [--base-seed N] [--rehearse]

For each of --seeds seeds (base-seed, base-seed + 1, ...) the cell's
system is built with that seed's weights and traffic, served for a short
window at the cell's own load and sizes, and the served tokens of the
run's sample are compared with the float32 reference: the program's widest
normalized logit gap (the lower reading). For the first --control seeds
the fp8 control is read at the same positions as well (the upper reading).
Both are judged by the harness's own comparison (`check.judge`) against
the configuration's limit: `correct` for the program, `control_correct`
for the control, which has to come out false. Set-up is paid once; later
seeds reuse the compiled steps.

Prints one line per seed and, last, a JSON summary.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as RUN                                             # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--base-seed", type=int, default=1_000_003)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell, config, tspec, _, _ = RUN.load_cell(args.workload, args.rehearse)
    jax = RUN.setup_jax(cell["chips"] if args.rehearse else 1)
    from harness import check, serve
    from harness.traffic import Traffic

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        RUN.log(f"calibrate.py: needs a TPU, JAX found "
                f"{devices[0].platform!r}")
        return 1
    used = devices[:cell["chips"]]
    ref = importlib.import_module(f"references.{config['family']}")
    adapter = importlib.import_module(f"adapters.{config['family']}")
    clock = serve.CompileClock()
    limit = config["correct"]["logit_gap_limit"]
    rows = []
    for i in range(args.seeds):
        seed = args.base_seed + i
        t0 = time.perf_counter()
        traffic = Traffic(tspec, seed, config["vocab_size"])
        sysm = serve.build(config, traffic, seed, used, adapter)
        if i == 0:
            serve.warm_up(sysm, traffic)
        d = serve.drive(sysm, traffic, args.seconds, clock)
        finished = {rid: (r.prompt, r.tokens) for rid, r in d.reqs.items()
                    if r.tokens is not None}
        chunk = sysm.chunk
        sysm.stack.close()
        del sysm
        gc.collect()
        picked = check.sample(finished, seed, chunk)
        g = check.served_gaps(ref, config, seed,
                              [finished[r][0] for r in picked],
                              [finished[r][1] for r in picked],
                              control=i < args.control)
        gap = check.widest(g["gap"])
        row = {"seed": seed, "requests": len(picked),
               "tokens": int(len(g["gap"])), "gap": gap,
               "correct": check.judge(gap, limit),
               "seconds": time.perf_counter() - t0}
        if "control_gap" in g:
            row["control_gap"] = check.widest(g["control_gap"])
            row["control_correct"] = check.judge(row["control_gap"], limit)
        rows.append(row)
        print(json.dumps(row), flush=True)
    gaps = [r["gap"] for r in rows]
    ctl = [r["control_gap"] for r in rows if "control_gap" in r]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": max(gaps), "upper": min(ctl) if ctl else None,
                      "limit": limit,
                      "correct": [r["correct"] for r in rows],
                      "control_correct": [r["control_correct"] for r in rows
                                          if "control_correct" in r],
                      "compiles": clock.count,
                      "device": used[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
