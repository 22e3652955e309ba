"""The reduction from a profiler trace to what the per-layer readers use.

A trace is read with `jax.profiler.ProfileData` (nothing but JAX). Device
planes are those named `/device:<PLATFORM>:<n>`; on each, the `XLA Ops`
line holds one event per operation that ran and the `XLA Modules` line one
event per program execution. The benchmark's own host spans
(`bench.tick`, `bench.exec.<kind>`, `bench.loadgen`) are read from the host
plane. All times are nanoseconds on the trace's one timeline.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")


@dataclass
class Device:
    ops: List[Interval] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Interval]                      # the benchmark's spans
    window: Tuple[float, float]               # first tick start .. last end

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clip(self, iv: List[Interval]) -> List[Interval]:
        a, b = self.window
        return [(max(s, a), min(e, b), n) for s, e, n in iv
                if e > a and s < b]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and m.group(1) != "CPU":
            dev = devices.setdefault(int(m.group(2)), Device())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops += [(e.start_ns, e.end_ns, e.name)
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    dev.modules += [(e.start_ns, e.end_ns, e.name)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith("bench.")]
    ticks = [iv for iv in host if iv[2] == "bench.tick"]
    if not ticks:
        raise RuntimeError("the trace holds no bench.tick span")
    window = (min(s for s, _, _ in ticks), max(e for _, e, _ in ticks))
    for d in devices.values():
        d.ops.sort()
        d.modules.sort()
    host.sort()
    return Trace(devices=devices, host=host, window=window)


def union(iv: List[Interval]) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals covering `iv`."""
    out: List[List[float]] = []
    for s, e, _ in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace, device: int) -> float:
    return sum(e - s for s, e in union(tr.clip(tr.devices[device].ops))
               ) * 1e-9


def mean_busy_s(tr: Trace, devices: List[int]) -> float:
    """Busy seconds averaged over the devices a run used (their ids); a
    device of the trace that the run did not use counts nothing."""
    return sum(busy_s(tr, d) for d in devices) / len(devices)


def short_op(text: str) -> Tuple[str, str]:
    """An operation's HLO text cut to its name, result type and opcode
    (`%copy.83 = bf16[12,364,128,8,128] copy`), layouts dropped; and the
    opcode alone."""
    bare = re.sub(r"\{[^{}]*\}", "", text)
    m = re.match(r"(%\S+) = (.+?) ([\w\-]+)\(", bare)
    if not m:
        return bare[:160], ""
    return f"{m.group(1)} = {m.group(2)[:100]} {m.group(3)}", m.group(3)


def _module_index(tr: Trace, device: int):
    mods = tr.devices[device].modules
    return [s for s, _, _ in mods], mods


def module_of(index, t: float) -> str:
    """The program (`XLA Modules` event) running at device time t."""
    starts, mods = index
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return mods[i][2].split("(", 1)[0]
    return "?"


def top_ops(tr: Trace, device: int, n: int = 10) -> List[List]:
    """The operations that took the most device time, named by program and
    HLO instruction. Loops are left out: their time is their body's."""
    index = _module_index(tr, device)
    tot: Dict[str, float] = {}
    for s, e, name in tr.clip(tr.devices[device].ops):
        op, opcode = short_op(name)
        if opcode in ("while", "conditional"):
            continue
        k = f"{module_of(index, s)}: {op}"
        tot[k] = tot.get(k, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:n]


def _host_label(tr: Trace, t: float) -> str:
    """The innermost benchmark span covering time t."""
    best: Optional[Interval] = None
    for s, e, name in tr.host:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    if best is None:
        return "outside the engine"
    return {"bench.tick": "engine host work",
            "bench.loadgen": "load generator"}.get(
        best[2], best[2].replace("bench.exec.", "executor host work: "))


def idle_gaps(tr: Trace, device: int, n: int = 10) -> List[List]:
    """The longest device idle gaps in the window, each labelled by what
    the host was doing at its midpoint."""
    a, b = tr.window
    busy = union(tr.clip(tr.devices[device].ops))
    gaps, cur = [], a
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if b > cur:
        gaps.append((cur, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(tr, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:n]]


def modules(tr: Trace, device: int, prefix: str) -> List[Interval]:
    """Program executions whose name starts with `prefix` (jit_<fn>), whole
    ones inside the window."""
    a, b = tr.window
    return [iv for iv in tr.devices[device].modules
            if iv[2].startswith(prefix) and iv[0] >= a and iv[1] <= b]


def ops_in(tr: Trace, device: int, module_prefix: str,
           needle: str) -> List[Interval]:
    """Operations whose HLO text contains `needle`, run inside a program
    whose name starts with `module_prefix`, whole ones in the window. A
    Pallas kernel is a `tpu_custom_call` in its step's program."""
    a, b = tr.window
    index = _module_index(tr, device)
    return [iv for iv in tr.devices[device].ops
            if needle in iv[2] and iv[0] >= a and iv[1] <= b
            and module_of(index, iv[0]).startswith(module_prefix)]
