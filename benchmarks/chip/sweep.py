#!/usr/bin/env python3
"""Find an open-loop cell's knee once: serve its traffic at several fixed
rates in one process and print what each rate did.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2,3,4 \
        --seconds 20 [--seed N]

For each rate the engine starts empty, ramps, measures for --seconds and
drains as a run does. A rate is sustained when the backlog (requests
arrived but not yet admitted) does not grow through the window and time to
first token stays bounded; the knee is the highest such rate. The cell's
traffic file then fixes its rate at about 0.8 of the knee. Prints one JSON
line per rate.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as RUN                                             # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=20_000_003)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell, config, tspec, _, _ = RUN.load_cell(args.workload, args.rehearse)
    if tspec["loop"] != "open":
        ap.error("a sweep needs an open-loop traffic mix")
    jax = RUN.setup_jax(cell["chips"] if args.rehearse else 1)
    from harness import serve
    from harness.stats import percentile
    from harness.traffic import Traffic

    devices = jax.devices()
    used = devices[:cell["chips"]]
    adapter = importlib.import_module(f"adapters.{config['family']}")
    clock = serve.CompileClock()
    sysm = None
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = Traffic({**tspec, "rate_per_s": rate}, args.seed,
                          config["vocab_size"])
        if sysm is None:
            sysm = serve.build(config, traffic, args.seed, used, adapter)
            serve.warm_up(sysm, traffic)
        else:
            sysm.executor.reset()
            serve.fresh_engine(sysm)
        d = serve.drive(sysm, traffic, args.seconds, clock)
        ws, we = d.window
        due = [r for r in d.reqs.values() if ws <= r.due < we]
        ttft = [r.stamps[0] - r.due for r in due if r.stamps]
        gaps = [b - a for r in d.reqs.values()
                for a, b in zip(r.stamps, r.stamps[1:]) if ws <= b < we]
        half = (ws + we) / 2
        early = [r.stamps[0] - r.due for r in due
                 if r.stamps and r.due < half]
        late = [r.stamps[0] - r.due for r in due
                if r.stamps and r.due >= half]
        done = sum(1 for r in d.reqs.values()
                   if r.stamps and ws <= r.stamps[-1] < we
                   and r.tokens is not None)
        toks = sum(1 for r in d.reqs.values() for t in r.stamps
                   if ws <= t < we)
        print(json.dumps({
            "rate": rate, "due": len(due),
            "no_first_token": sum(1 for r in due if not r.stamps),
            "finished_per_s": done / args.seconds,
            "tok_s": toks / args.seconds,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
            "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else None,
            "ttft_p50_first_half_ms": (1e3 * percentile(early, 50)
                                       if early else None),
            "ttft_p50_second_half_ms": (1e3 * percentile(late, 50)
                                        if late else None),
            "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
            "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
            "compiles_in_window": d.compiles_in_window}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
