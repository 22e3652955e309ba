"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init)."""
from __future__ import annotations

from typing import Mapping, Optional, Sequence

import jax

# Physical placement order for planned meshes: the weakest links go
# outermost (pipelining tolerates them; DESIGN.md §5), TP innermost.
CANONICAL_AXES = ("pod", "pipe", "data", "model")


def _make_mesh(shape, axes, devices=None):
    """A mesh whose axes are all Auto: shardings propagate through GSPMD and
    the model's `shard` hooks add constraints."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-mesh)."""
    return _make_mesh(tuple(shape), tuple(axes))


def build_mesh(mesh_shape: Mapping[str, int],
               devices: Optional[Sequence] = None):
    """Build a *planned* mesh from an {axis: size} dict (the ExecutionPlan
    output): axes ordered canonically (pod, pipe, data, model — unknown
    axes last), over the first prod(sizes) of `devices` (default
    jax.devices()), so a plan smaller than the host still builds."""
    items = sorted(mesh_shape.items(),
                   key=lambda kv: (CANONICAL_AXES.index(kv[0])
                                   if kv[0] in CANONICAL_AXES
                                   else len(CANONICAL_AXES), kv[0]))
    axes = tuple(a for a, _ in items)
    sizes = tuple(int(n) for _, n in items)
    n = 1
    for s in sizes:
        n *= s
    devices = list(jax.devices()) if devices is None else list(devices)
    if n > len(devices):
        raise ValueError(f"planned mesh {dict(mesh_shape)} needs {n} "
                         f"devices; only {len(devices)} available")
    return _make_mesh(sizes, axes, devices=devices[:n])
