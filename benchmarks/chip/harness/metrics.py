"""Per-layer metrics: one reader file per metric, found by its name.

`metrics/<name>.py` defines `read(ctx) -> float | None`. A reader that
finds nothing to read returns None, and the metric is left out of the
result line; a share of a roofline or of a peak is never returned as 0.
"""
from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS_DIR = os.path.join(os.path.dirname(HERE), "metrics")


@dataclass
class Context:
    """What a reader may use."""
    config: Dict                 # the configuration file
    ref: object                  # the configuration's reference module
    devices: List[int]           # ids of the chips the run's mesh is on
    peaks: Optional[Dict]        # the chip's peaks (None off a chip)
    trace: Optional[object]      # harness.trace.Trace of the traced window
    calls: List                  # executor calls made while tracing
    ticks: List                  # engine ticks inside the window
    counters: Dict               # engine counters at window start and end
    memory_peak_bytes: Optional[int]
    promised_bytes: float        # the plan's Eq. 11 requirement
    kv_block: int


def reader(name: str):
    path = os.path.join(METRICS_DIR, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(names: List[str], ctx: Context) -> Dict[str, float]:
    out = {}
    for name in names:
        value = reader(name)(ctx)
        if value is not None:
            out[name] = value
    return out
