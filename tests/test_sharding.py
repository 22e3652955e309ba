"""Sharding rules and distributed execution (subprocess with 8 fake devices):
param specs divide evenly, distributed train step matches single-device,
SP/EP strategies compile."""
import pytest

from conftest import run_in_subprocess

# subprocess + XLA compiles => slow tier
pytestmark = pytest.mark.slow

_is_spec = None  # placeholder (subprocess snippets define their own)

from repro.configs import get_config
from repro.launch.compile import abstract_params
from repro.parallel import sharding as S


def test_specs_cover_all_params_single_device():
    """On a trivial mesh every spec must be fully replicated (no axes)."""
    import jax
    from repro.launch.mesh import make_mesh
    cfg = get_config("mixtral-8x7b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = S.param_specs(cfg, abstract_params(cfg),
                          S.Strategy(), mesh)
    for spec in jax.tree.leaves(specs,
                                is_leaf=_is_spec):
        pass  # building specs must not raise
    assert specs is not None


def test_distributed_matches_single_device():
    """The same train step on a (2,2) mesh and on 1 device gives the same
    loss/params — the SPMD-correctness cornerstone."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig, TRAIN
from repro.launch.mesh import make_mesh
from repro.launch import compile as LC
from repro.models import init_params
from repro.optim import optimizers as opt
from repro.runtime.train_step import TrainStepConfig, make_train_step
from repro.parallel.axes import axis_rules
from repro.data.pipeline import DataConfig, TokenPipeline

cfg = get_config("h2o-danube-1.8b").reduced()
tcfg = TrainStepConfig(remat="dots", microbatches=2,
                       optimizer=opt.OptimizerConfig(lr=1e-3),
                       warmup_steps=1, total_steps=10)
params = init_params(jax.random.PRNGKey(0), cfg)
opt_state = opt.init_state(tcfg.optimizer, params)
pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=4, seed=1))
batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}

# single device
step1 = jax.jit(make_train_step(cfg, tcfg))
p1, o1, m1 = step1(params, opt_state, batch, jnp.asarray(0))

# distributed
mesh = make_mesh((2, 2), ("data", "model"))
strategy = __import__("repro.parallel.sharding", fromlist=["x"])\\
    .default_strategy(cfg, mesh)
with mesh, axis_rules(strategy.rules(), mesh=mesh):
    stepN = jax.jit(make_train_step(cfg, tcfg))
    pN, oN, mN = stepN(params, opt_state, batch, jnp.asarray(0))

assert abs(float(m1["loss"]) - float(mN["loss"])) < 2e-2, \\
    (float(m1["loss"]), float(mN["loss"]))
d = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pN)))
assert d < 0.05, d
print("DIST_MATCH_OK", float(m1["loss"]), float(mN["loss"]))
"""
    out = run_in_subprocess(code, devices=8)
    assert "DIST_MATCH_OK" in out


def test_production_specs_divide_evenly():
    """Every param/cache/input spec must divide its dim on the production
    mesh for ALL archs (the exact check jit enforces at lower time)."""
    code = """
import jax
from repro.configs import ARCH_IDS, SHAPES, get_config, input_specs
from repro.launch.mesh import make_mesh
from repro.launch.compile import abstract_params
from repro.models.model import init_cache
from repro.parallel import sharding as S
from jax.sharding import PartitionSpec as _P

def _is_spec(x):
    return isinstance(x, _P)

mesh = make_mesh((2, 4), ("data", "model"))
for arch in ARCH_IDS:
    cfg = get_config(arch)
    strategy = S.default_strategy(cfg, mesh)
    pa = abstract_params(cfg)
    specs = S.param_specs(cfg, pa, strategy, mesh)
    flat_p = jax.tree.leaves(pa)
    flat_s = jax.tree.leaves(specs, is_leaf=_is_spec)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (arch, leaf.shape, spec)
    cache = init_cache(cfg, 16, 2048, abstract=True)
    cspecs = S.cache_specs(cfg, cache, strategy, mesh)
    for leaf, spec in zip(jax.tree.leaves(cache),
                          jax.tree.leaves(cspecs,
                                          is_leaf=_is_spec)):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (arch, "cache", leaf.shape, spec)
print("DIVIS_OK")
"""
    out = run_in_subprocess(code, devices=8)
    assert "DIVIS_OK" in out


def test_ep_strategy_and_compressed_psum():
    """EP sharding compiles for MoE; compressed_psum matches plain mean."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim.compress import compressed_psum
mesh = make_mesh((8,), ("data",))
x = jnp.arange(64.0).reshape(8, 8) / 7.0

@partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
def f(xs):
    key = jax.random.PRNGKey(0)
    return compressed_psum(xs, "data", key)

got = f(x)
want = jnp.broadcast_to(x.mean(axis=0, keepdims=True), x.shape)
err = float(jnp.abs(got - want).max())
scale = float(jnp.abs(x).max()) / 127.0
assert err <= scale * 1.5 + 1e-6, (err, scale)
print("PSUM_OK", err)
"""
    out = run_in_subprocess(code, devices=8)
    assert "PSUM_OK" in out
