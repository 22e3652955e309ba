#!/usr/bin/env python3
"""Where a traced run's device idle time falls among the program's spans.

    python3 benchmarks/chip/idle_split.py <trace.xplane.pb> [...]

Takes the profiler trace of a run with the program's spans (`run.py
--trace 1 --keep-trace DIR` keeps it) and prints one JSON object a trace:

- `offset_ms`: the bracket (low, high) on how early the device's timeline
  reads against the host's. A program cannot start on the device before the
  host's `tpu::System::Execute` that submits it, nor end after the host's
  `tpu::System::Execute=>Done` for it, so the offset is at least the
  largest (submit - start) and at most the smallest (done - end), pairing
  programs with the runtime's events in order. Null when their counts
  differ.
- `as_traced` and `shifted`: the window's device idle time split by the
  innermost program span over it (`executor.<kind>.pack/.launch/.pull`,
  other executor time, `engine.step` outside every `executor.*` span, and
  outside the engine), as shares of the idle time; `shifted` first moves
  the device's timeline later by the middle of the bracket.
- `longest`: the longest idle gaps as traced, each with the innermost
  program span and benchmark span at its middle.
- `engine.step_self_ms`, `executor.decode_launch_ms` and
  `executor.decode_tail_ms`: the program's own host time per tick, before
  a decode step's launch, and after its device work was seen done
  (`step_self_ms`, `decode_launch_ms` and `decode_tail_ms` below).

`harness.trace.load` keeps only the benchmark's host spans, so `load` here
adds the program's spans and the runtime's events to its `Trace`.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import trace as T                                # noqa: E402

KINDS = ("decode", "chunk", "prefill", "reset")
PHASES = ("pack", "launch", "pull")
PROGRAM = ("engine.", "executor.")          # prefixes of the program's spans
SUBMIT = "tpu::System::Execute"             # the host enqueues a program
DONE = "tpu::System::Execute=>Done"         # the host sees it finished
Span = Tuple[float, float]


@dataclass
class Traced(T.Trace):
    """A trace with the program's spans and the runtime's host events."""
    program: List[T.Interval] = field(default_factory=list)
    runtime: List[T.Interval] = field(default_factory=list)


def load(path: str) -> Traced:
    """`harness.trace.load`, plus the `/host:` planes' events named with a
    `PROGRAM` prefix (none for a program without spans) and the runtime's
    `SUBMIT` and `DONE` events, each list sorted."""
    from jax.profiler import ProfileData
    program: List[T.Interval] = []
    runtime: List[T.Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    program.append((e.start_ns, e.end_ns, e.name))
                elif e.name in (SUBMIT, DONE):
                    runtime.append((e.start_ns, e.end_ns, e.name))
    return Traced(**vars(T.load(path)), program=sorted(program),
                  runtime=sorted(runtime))


def program_spans(tr: Traced, name: str, children: str = ""
                  ) -> List[Tuple[T.Interval, List[T.Interval], float]]:
    """The program's spans called `name` wholly inside the window, each with
    its child spans (the program's spans inside it whose names start with
    `children`; none when `children` is empty) and its self time in ns:
    its duration minus the union of those children."""
    a, b = tr.window
    out = []
    for sp in tr.program:
        if sp[2] != name or sp[0] < a or sp[1] > b:
            continue
        kids = [iv for iv in tr.program if children and iv is not sp
                and iv[2].startswith(children)
                and sp[0] <= iv[0] and iv[1] <= sp[1]]
        own = sp[1] - sp[0] - sum(e - s for s, e in T.union(kids))
        out.append((sp, kids, own))
    return out


def step_self_ms(tr: Traced) -> Optional[float]:
    """The engine's own host time per tick: the mean over `engine.step`
    spans of each span's duration minus the union of the `executor.*`
    spans inside it."""
    steps = program_spans(tr, "engine.step", "executor.")
    if not steps:
        return None
    return 1e-6 * sum(own for _, _, own in steps) / len(steps)


def decode_launch_ms(tr: Traced) -> Optional[float]:
    """Host time before the chip can begin a decode step: the median over
    `executor.decode` spans of the time from the span's start to the end of
    its `executor.decode.launch` child (packing, host-to-device copies and
    the step's dispatch)."""
    spans = program_spans(tr, "executor.decode", "executor.decode.launch")
    launch = [max(e for _, e, _ in kids) - sp[0]
              for sp, kids, _ in spans if kids]
    if not launch:
        return None
    return 1e-6 * statistics.median(launch)


def decode_tail_ms(tr: Traced) -> Optional[float]:
    """Host time after a decode call's device work was seen done: the
    median over `executor.decode` spans of the time from the end of the
    last `DONE` event inside the span (the host seeing the call's last
    program finish) to the span's end (reading the tokens back and
    unpacking them). Both ends are on the host's clock."""
    done = [iv for iv in tr.runtime if iv[2] == DONE]
    tails = []
    for sp, _, _ in program_spans(tr, "executor.decode"):
        ends = [e for s, e, _ in done if sp[0] <= s and e <= sp[1]]
        if ends:
            tails.append(sp[1] - max(ends))
    if not tails:
        return None
    return 1e-6 * statistics.median(tails)


def idle(tr: T.Trace, shift: float = 0.0, device: int = 0) -> List[Span]:
    """The window's intervals with no operation on the device, its timeline
    moved `shift` ns later."""
    a, b = tr.window
    busy = T.union([(s + shift, e + shift, n)
                    for s, e, n in tr.devices[device].ops])
    out, cur = [], a
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, b)))
        cur = max(cur, e)
        if cur >= b:
            break
    if b > cur:
        out.append((cur, b))
    return [(s, e) for s, e in out if e > s]


def overlap(u: List[Span], v: List[Span]) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(u) and j < len(v):
        tot += max(0.0, min(u[i][1], v[j][1]) - max(u[i][0], v[j][0]))
        if u[i][1] < v[j][1]:
            i += 1
        else:
            j += 1
    return tot


def split(tr: Traced, shift: float = 0.0) -> Dict[str, float]:
    """Shares of the idle time in each program span (innermost first)."""
    gaps = idle(tr, shift)
    tot = sum(e - s for s, e in gaps)

    def within(names) -> float:
        return overlap(gaps, T.union([iv for iv in tr.program
                                      if iv[2] in names]))

    out: Dict[str, float] = {}
    for k in KINDS:
        for p in PHASES:
            out[f"executor.{k}.{p}"] = within({f"executor.{k}.{p}"})
    calls = within({f"executor.{k}" for k in KINDS})
    step = within({"engine.step"})
    out["other executor"] = calls - sum(out.values())
    out["engine.step self"] = step - calls
    out["outside the engine"] = tot - step
    out = {k: v / tot for k, v in out.items() if v > 0}
    out["all executor.*"] = calls / tot
    out["idle_ms"] = tot * 1e-6
    out["idle_share"] = tot / (tr.window[1] - tr.window[0])
    return out


def offset_bracket(tr: Traced, device: int = 0
                   ) -> Optional[Tuple[float, float]]:
    """(low, high) ns by which the device's timeline reads early."""
    mods = tr.devices[device].modules
    sub = [iv for iv in tr.runtime if iv[2] == SUBMIT]
    done = [iv for iv in tr.runtime if iv[2] == DONE]
    if not mods or not len(mods) == len(sub) == len(done):
        return None
    return (max(x[0] - m[0] for x, m in zip(sub, mods)),
            min(d[0] - m[1] for d, m in zip(done, mods)))


def _innermost(spans, t: float) -> Optional[str]:
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best and best[2]


def longest(tr: Traced, n: int = 3) -> List[list]:
    out = []
    for s, e in sorted(idle(tr), key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        out.append([(e - s) * 1e-6, _innermost(tr.program, mid),
                    _innermost(tr.host, mid)])
    return out


def reduce(tr: Traced) -> dict:
    bracket = offset_bracket(tr)
    out = {"offset_ms": bracket and [x * 1e-6 for x in bracket],
           "as_traced": split(tr)}
    if bracket:
        out["shifted"] = split(tr, shift=sum(bracket) / 2)
    out["longest"] = longest(tr)
    out["engine.step_self_ms"] = step_self_ms(tr)
    out["executor.decode_launch_ms"] = decode_launch_ms(tr)
    out["executor.decode_tail_ms"] = decode_tail_ms(tr)
    return out


def main(argv=None) -> int:
    paths = sys.argv[1:] if argv is None else argv
    for path in paths:
        print(json.dumps({"trace": path, **reduce(load(path))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
