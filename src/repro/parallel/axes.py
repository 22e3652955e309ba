"""Logical-axis system: models annotate tensors with *logical* axis names;
a strategy maps logical names onto physical mesh axes (MaxText-style).

Activations call `shard(x, "batch", "seq", "embed")`; weights get their
PartitionSpec from `parallel.sharding` path rules. Outside a mesh context the
hooks are identity, so the same model code runs on 1 CPU device in tests.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Axis = Union[None, str, Tuple[str, ...]]

# Default logical->physical rules for the production 2-D/3-D meshes.
# "pod" is present only in the multi-pod mesh; missing axes are dropped.
DEFAULT_RULES: Dict[str, Axis] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,             # sequence kept local by default (SP overrides)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "inner": "model",        # xLSTM inner (v/output) dim
    "lru": "model",          # RG-LRU width
    "mlp_act": "model",
    "kv_seq": None,          # KV-cache sequence dim (SP decode overrides -> "model")
    # weights
    "embed_w": "data",       # FSDP axis for the d_model dim of weights
    "mlp": "model",          # TP axis for FFN hidden
    "q_w": "model",          # TP for flattened q/o projection dim (heads*hd)
    "kv_w": "model",         # TP for flattened k/v projection dim
    "vocab": "model",
    "experts": None,         # experts dim (EP strategy overrides -> "model")
    "layers": None,          # stacked-scan leading dim
    "conv": None,
}

_state = threading.local()


def current_rules() -> Dict[str, Axis]:
    return getattr(_state, "rules", DEFAULT_RULES)


def current_mesh() -> Optional[Mesh]:
    m = getattr(_state, "mesh", None)
    if m is not None:
        return m
    # fall back to the ambient jax mesh context if one is active
    env = jax._src.mesh.thread_resources.env  # noqa: SLF001
    phys = env.physical_mesh
    return phys if phys and not phys.empty else None


@contextlib.contextmanager
def suspend_annotations():
    """Disable shard()/gather_fsdp() for code traced inside this context.

    Needed for manual-parallelism regions (shard_map bodies, e.g. the 1F1B
    pipeline stage): the per-device code is already local, and a
    with_sharding_constraint naming a manual mesh axis is an error there.
    Trace-time only — the flag is read while jax traces, not at run time.
    """
    old = getattr(_state, "suspended", False)
    _state.suspended = True
    try:
        yield
    finally:
        _state.suspended = old


def annotations_suspended() -> bool:
    return getattr(_state, "suspended", False)


@contextlib.contextmanager
def axis_rules(rules: Dict[str, Axis], mesh: Optional[Mesh] = None):
    old_rules = getattr(_state, "rules", None)
    old_mesh = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        if old_rules is None:
            del _state.rules
        else:
            _state.rules = old_rules
        if old_mesh is None:
            if hasattr(_state, "mesh"):
                del _state.mesh
        else:
            _state.mesh = old_mesh


def logical_to_spec(logical: Sequence[Optional[str]],
                    rules: Optional[Dict[str, Axis]] = None,
                    mesh: Optional[Mesh] = None,
                    shape: Optional[Sequence[int]] = None) -> P:
    """Map logical axis names to a PartitionSpec under `rules` and `mesh`.

    With `shape`, mesh axes that do not evenly divide their dimension are
    dropped (jit in_shardings demand exact divisibility; GSPMD propagation
    still finds split tilings internally — DESIGN.md §4 head-divisibility).
    """
    rules = rules if rules is not None else current_rules()
    mesh = mesh if mesh is not None else current_mesh()
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    used = set()
    out = []
    for i, name in enumerate(logical):
        ax = rules.get(name) if name is not None else None
        if ax is None:
            out.append(None)
            continue
        cands = (ax,) if isinstance(ax, str) else tuple(ax)
        picked = []
        dim = shape[i] if shape is not None else None
        for a in cands:
            if a not in mesh_axes or a in used:
                continue
            if dim is not None:
                size = mesh.shape[a]
                if dim % (size * int(np_prod([mesh.shape[p] for p in picked])
                                     or 1)):
                    continue
            picked.append(a)
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    return P(*out)


def np_prod(xs):
    r = 1
    for x in xs:
        r *= x
    return r


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axes (no-op outside a mesh)."""
    if annotations_suspended():
        return x
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(logical, mesh=mesh, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def gather_fsdp(w: jax.Array, *logical: Optional[str]) -> jax.Array:
    """ZeRO-3 gather-on-use: re-constrain a weight with its FSDP ("embed_w")
    axis dropped, so GSPMD all-gathers the (small) weight over "data" instead
    of psum-ing the (large) activation partials — EXPERIMENTS §Perf iter 2."""
    if annotations_suspended():
        return w
    mesh = current_mesh()
    if mesh is None:
        return w
    rules = dict(current_rules())
    rules["embed_w"] = None
    spec = logical_to_spec(logical, rules=rules, mesh=mesh, shape=w.shape)
    return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, spec))
