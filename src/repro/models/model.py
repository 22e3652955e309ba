"""Model assembly: embedding -> scan(pattern units) -> tail -> norm -> head.

The depth pattern (configs.base: unit × repeats + tail) is the lax.scan unit:
parameters and caches are *stacked over repeats* per unit position, so
heterogeneous patterns (gemma3 5:1, griffin rec-rec-attn, xLSTM 7:1) scan
with uniform bodies. The runtime injects remat around the unit body.

All mixers follow the delta convention: they return the residual increment.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, MLSTM, RGLRU, SLSTM, MLP_DENSE,
                                MLP_MOE, BlockSpec, ModelConfig)
from repro.models import attention, layers, moe, recurrent
from repro.parallel.axes import shard


@dataclasses.dataclass(frozen=True)
class ModelSettings:
    attn: attention.AttnSettings = attention.AttnSettings()
    mlstm_backend: Optional[str] = None     # None => kernels.ops default
    mlstm_chunk: int = 128
    build_cache: bool = False               # prefill returns a filled cache
    scan_layers: bool = True                # False: unroll (exact HLO cost
                                            # accounting — roofline/analysis)
    embed_onehot: bool = True               # matmul embedding lookup — on a
                                            # vocab-sharded table this avoids
                                            # the gather's involuntary full
                                            # resharding (§Perf iter 3;
                                            # gemma3 train T_mem −20%)
    moe_group: int = 2048                   # MoE routing group size —
                                            # dispatch FLOPs/bytes ∝ group


def serving_settings() -> ModelSettings:
    """The serving steps' settings when the caller passes none: attention
    takes this process's kernel backend (kernels.ops.default_backend) —
    the compiled Pallas kernels on a TPU for whole-prompt prefill, paged
    decode and paged chunked prefill; the blocked jnp paths elsewhere."""
    from repro.kernels import ops as kops
    return ModelSettings(
        attn=attention.AttnSettings(backend=kops.default_backend()))


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    mult = 2 if layers.is_glu(cfg.activation) else 1
    ki, ko = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "norm": layers.rmsnorm_init(d, dt),
        "wi": layers.dense_init(ki, d, mult * f, dt),
        "wo": layers.dense_init(ko, f, d, dt),
    }


def mlp_apply(params, cfg: ModelConfig, x, gather_weights: bool = False):
    from repro.parallel.axes import gather_fsdp
    wi, wo = params["wi"], params["wo"]
    if gather_weights:
        wi = gather_fsdp(wi, None, "mlp")
        wo = gather_fsdp(wo, "mlp", None)
    h = layers.rmsnorm(params["norm"], x, cfg.norm_eps)
    up = layers.matmul(h, wi)
    up = shard(up, "batch", "seq", "mlp_act")
    if layers.is_glu(cfg.activation):
        gate, val = jnp.split(up, 2, axis=-1)
        act = layers.glu_combine(cfg.activation, gate, val)
    else:
        act = layers.ACTIVATIONS[cfg.activation](up)
    y = layers.matmul(act, wo)
    return shard(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Block = mixer + channel mixer
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, blk: BlockSpec):
    km, kc = jax.random.split(key)
    p: Dict[str, Any] = {}
    if blk.mixer == ATTN:
        p["mixer"] = attention.attn_init(km, cfg)
    elif blk.mixer == MLSTM:
        p["mixer"] = recurrent.mlstm_init(km, cfg)
    elif blk.mixer == SLSTM:
        p["mixer"] = recurrent.slstm_init(km, cfg)
    elif blk.mixer == RGLRU:
        p["mixer"] = recurrent.rglru_init(km, cfg)
    if blk.mlp == MLP_DENSE:
        p["mlp"] = mlp_init(kc, cfg)
    elif blk.mlp == MLP_MOE:
        p["mlp"] = moe.moe_init(kc, cfg)
    return p


def block_cache_init(cfg: ModelConfig, blk: BlockSpec, batch: int,
                     context: int, abstract: bool = False):
    if blk.mixer == ATTN:
        fn = attention.cache_spec if abstract else attention.cache_init
        return fn(cfg, blk, batch, context)
    if blk.mixer == MLSTM:
        return recurrent.mlstm_state_init(cfg, batch, abstract)
    if blk.mixer == SLSTM:
        return recurrent.slstm_state_init(cfg, batch, abstract)
    if blk.mixer == RGLRU:
        return recurrent.rglru_state_init(cfg, batch, abstract)
    raise ValueError(blk.mixer)


def block_apply(params, cfg: ModelConfig, blk: BlockSpec, x, positions,
                cache=None, decode: bool = False, context: int = 0,
                settings: ModelSettings = ModelSettings(),
                block_tables=None, layer=None):
    """Returns (x', new_cache, aux). A paged attention cache is the
    layer-stacked pool, and `layer` this block's index in it."""
    aux = _zero_aux()
    building = settings.build_cache and not decode and cache is None
    if blk.mixer == ATTN:
        cache_arg = cache if cache is not None else ("build" if building
                                                     else None)
        delta, new_cache, attn_aux = attention.attn_apply(
            params["mixer"], cfg, blk, x, positions, cache=cache_arg,
            decode=decode, context=context, settings=settings.attn,
            block_tables=block_tables, layer=layer)
        aux = {**aux, **attn_aux}
    else:
        if building:  # prefill: recurrent blocks start from zero state
            cache = block_cache_init(cfg, blk, x.shape[0], context)
        if blk.mixer == MLSTM:
            delta, new_cache = recurrent.mlstm_apply(
                params["mixer"], cfg, x, state=cache, decode=decode,
                backend=settings.mlstm_backend, chunk=settings.mlstm_chunk,
                positions=positions)
        elif blk.mixer == SLSTM:
            delta, new_cache = recurrent.slstm_apply(
                params["mixer"], cfg, x, state=cache, decode=decode,
                positions=positions)
        elif blk.mixer == RGLRU:
            delta, new_cache = recurrent.rglru_apply(
                params["mixer"], cfg, x, state=cache, decode=decode,
                positions=positions)
        else:
            raise ValueError(blk.mixer)
        if decode and cache is not None:
            # full-width serving ticks include INERT rows (position -1:
            # empty lanes, lanes mid-chunk-prefill) — their pad-token
            # step must not advance the lane's recurrent state
            live = positions[:, 0] >= 0
            new_cache = jax.tree.map(
                lambda nw, old: jnp.where(
                    live.reshape((-1,) + (1,) * (nw.ndim - 1)), nw, old),
                new_cache, cache)
    x = x + delta
    if blk.mlp == MLP_DENSE:
        x = x + mlp_apply(params["mlp"], cfg, x,
                          gather_weights=settings.attn.gather_weights)
    elif blk.mlp == MLP_MOE:
        delta, aux = moe.moe_apply(params["mlp"], cfg, x,
                                   group_size=settings.moe_group)
        x = x + delta
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Parameter / cache trees
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, shardings=None):
    """Random parameters for `cfg`, built by ONE jitted program: XLA fuses
    each initializer's f32 normal draw into its cast to the param dtype, so
    no f32 copy of a whole (layer-stacked) weight is ever live — at full
    width that copy alone would outgrow a chip's HBM. `shardings` (a
    pytree of jax.sharding.Sharding matching the params) places every leaf
    directly on its devices; None leaves placement to jit."""
    if shardings is None:
        return _init_params_jit(key, cfg)
    return jax.jit(_init_params, static_argnums=1,
                   out_shardings=shardings)(key, cfg)


def _init_params(key, cfg: ModelConfig):
    keys = jax.random.split(key, 4)
    params: Dict[str, Any] = {"embed": layers.embed_init(keys[0], cfg)}

    def stacked_init(pos_key, blk):
        ks = jax.random.split(pos_key, max(cfg.repeats, 1))
        return jax.vmap(lambda k_: block_init(k_, cfg, blk))(ks)

    unit_keys = jax.random.split(keys[1], max(len(cfg.unit), 1))
    params["units"] = [stacked_init(unit_keys[i], blk)
                       for i, blk in enumerate(cfg.unit)]
    tail_keys = jax.random.split(keys[2], max(len(cfg.tail), 1))
    params["tail"] = [block_init(tail_keys[i], cfg, blk)
                      for i, blk in enumerate(cfg.tail)]
    params["final_norm"] = layers.rmsnorm_init(cfg.d_model,
                                               jnp.dtype(cfg.param_dtype))
    if not cfg.tie_embeddings:
        params["head"] = {"table": (jax.random.normal(
            keys[3], (cfg.padded_vocab_size, cfg.d_model), jnp.float32)
            * layers.INIT_STD).astype(jnp.dtype(cfg.param_dtype))}
    return params


_init_params_jit = jax.jit(_init_params, static_argnums=1)


def init_cache(cfg: ModelConfig, batch: int, context: int,
               abstract: bool = False):
    """Cache tree mirroring the params layout (stacked over repeats)."""
    def stacked(blk):
        one = block_cache_init(cfg, blk, batch, context, abstract=True)
        stack = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.repeats,) + s.shape, s.dtype),
            one)
        if abstract:
            return stack
        return jax.tree.map(lambda s: _materialize(s), stack)

    def _materialize(s):
        if s.dtype == jnp.int32:   # position buffers start invalid
            return jnp.full(s.shape, -1, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    cache = {"units": [stacked(blk) for blk in cfg.unit],
             "tail": []}
    for blk in cfg.tail:
        one = block_cache_init(cfg, blk, batch, context, abstract=True)
        cache["tail"].append(
            one if abstract else jax.tree.map(_materialize, one))
    return cache


# ---------------------------------------------------------------------------
# Staged forward pieces (pipeline runtime): embed | unit stack | tail + head
# ---------------------------------------------------------------------------

def _zero_aux():
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}


def unit_stack_forward(units_params, cfg: ModelConfig, x, pos, *,
                       settings: ModelSettings = ModelSettings(),
                       context: int = 0,
                       unit_wrapper: Callable = lambda f: f):
    """Forward through a slice of the stacked unit pattern (train path, no
    caches) — the 1F1B pipeline-stage body. `units_params` is the params
    layout of params["units"] (one tree per unit position, each stacked on a
    leading repeats dim, here the stage's own slice). Returns (x, aux_sum).
    """
    ctx = context or x.shape[1]

    def unit_body(x, unit_params):
        aux_sum = _zero_aux()
        for i, blk in enumerate(cfg.unit):
            x, _, aux = block_apply(unit_params[i], cfg, blk, x, pos,
                                    cache=None, decode=False, context=ctx,
                                    settings=settings)
            aux_sum = {k: aux_sum[k] + aux.get(k, 0) for k in aux_sum}
        return x, aux_sum

    unit_body = unit_wrapper(unit_body)

    def scan_body(carry, xs):
        x, aux_acc = carry
        x, aux = unit_body(x, list(xs))
        return (x, {k: aux_acc[k] + aux.get(k, 0) for k in aux_acc}), ()

    (x, aux_acc), _ = jax.lax.scan(scan_body, (x, _zero_aux()),
                                   tuple(units_params))
    return x, aux_acc


def tail_head_forward(params, cfg: ModelConfig, x, pos, *,
                      settings: ModelSettings = ModelSettings(),
                      context: int = 0):
    """The post-pipeline remainder: tail blocks -> final norm -> LM head.
    Returns (logits, aux_sum)."""
    ctx = context or x.shape[1]
    aux_acc = _zero_aux()
    for i, blk in enumerate(cfg.tail):
        x, _, aux = block_apply(params["tail"][i], cfg, blk, x, pos,
                                cache=None, decode=False, context=ctx,
                                settings=settings)
        aux_acc = {k: aux_acc[k] + aux.get(k, 0) for k in aux_acc}
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return layers.lm_head(head, cfg, x), aux_acc


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def apply(params, cfg: ModelConfig, tokens, *, positions=None,
          prefix_embeds=None, cache=None, decode: bool = False,
          settings: ModelSettings = ModelSettings(), context: int = 0,
          unit_wrapper: Callable = lambda f: f, logits_last_only: bool = False,
          block_tables=None):
    """Forward pass.

    tokens [b, s] (s=1 for decode); positions [b] for decode, [b, s]
    absolute positions for a mid-prompt chunk (else implied arange);
    prefix_embeds [b, p, d] for modality-stub archs; block_tables
    [b, max_blocks] maps each sequence's logical KV blocks to physical
    blocks of a paged pool cache (serving decode; -1 = unassigned).
    Returns (logits, new_cache_or_None, aux).
    """
    b = tokens.shape[0]
    x = layers.embed_lookup(params["embed"], cfg, tokens,
                            onehot=settings.embed_onehot)
    if prefix_embeds is not None and not decode:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    if decode:
        assert positions is not None
        pos = positions[:, None]                      # [b, 1]
    elif positions is not None:
        # explicit absolute positions [b, s] (chunked prefill appends a
        # mid-prompt slice; -1 marks padding)
        assert prefix_embeds is None
        pos = positions
    else:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    ctx = context or s

    zero_aux = _zero_aux()
    if decode and block_tables is not None and settings.attn.track_mass:
        # per-block attention mass, summed over layers (relative heat is
        # what the retention policy ranks on)
        zero_aux["attn_mass"] = jnp.zeros(
            (b, block_tables.shape[1]), jnp.float32)
    want_cache = decode or settings.build_cache
    have_cache = cache is not None
    # A paged pool leaf (attention.is_paged_cache) stays ONE layer-stacked
    # buffer that every layer updates in place at its own index: the layer
    # scan carries it whole. As scan xs/ys it would be sliced out for each
    # layer and stacked back, copying the whole pool every step. Per-lane
    # caches (rings, recurrent states) ride xs/ys.
    caches_in = cache["units"] if have_cache else [None] * len(cfg.unit)
    paged = [attention.is_paged_cache(c) for c in caches_in]

    def split(caches):
        """(paged pools, per-lane caches), None where the other holds it."""
        return ([c if pg else None for c, pg in zip(caches, paged)],
                [None if pg else c for c, pg in zip(caches, paged)])

    def join(pools, lanes):
        return [p if pg else c for p, c, pg in zip(pools, lanes, paged)]

    def unit_body(x, unit_params, unit_caches, layer):
        new_caches = []
        aux_sum = dict(zero_aux)
        for i, blk in enumerate(cfg.unit):
            c = unit_caches[i] if unit_caches is not None else None
            x, nc, aux = block_apply(unit_params[i], cfg, blk, x, pos,
                                     cache=c, decode=decode, context=ctx,
                                     settings=settings,
                                     block_tables=block_tables, layer=layer)
            new_caches.append(nc)
            aux_sum = {k: aux_sum[k] + aux.get(k, 0) for k in aux_sum}
        return x, new_caches, aux_sum

    unit_body = unit_wrapper(unit_body)

    if cfg.unit and settings.scan_layers and not have_cache \
            and not want_cache:
        # cache-free training forward: the same unit-stack scan the 1F1B
        # pipeline stages run (one implementation, so pipeline parity can
        # never drift from the sequential path)
        x, aux_acc = unit_stack_forward(params["units"], cfg, x, pos,
                                        settings=settings, context=ctx,
                                        unit_wrapper=unit_wrapper)
        new_unit_caches = None
    elif cfg.unit and settings.scan_layers:
        def scan_body(carry, xs):
            x, aux_acc, pools = carry
            layer, unit_params, lanes = xs
            unit_caches = join(pools, lanes) if have_cache else None
            x, new_caches, aux = unit_body(x, list(unit_params), unit_caches,
                                           layer)
            aux_acc = {k: aux_acc[k] + aux.get(k, 0) for k in aux_acc}
            pools, lanes = split(new_caches)
            ys = tuple(lanes) if want_cache else ()
            return (x, aux_acc, tuple(pools)), ys

        pools, lanes = split(caches_in)
        xs = (jnp.arange(cfg.repeats, dtype=jnp.int32),
              tuple(params["units"]), tuple(lanes))
        (x, aux_acc, pools), ys = jax.lax.scan(
            scan_body, (x, dict(zero_aux), tuple(pools)), xs)
        new_unit_caches = join(pools, ys) if want_cache else None
    elif cfg.unit:
        # Unrolled path: python loop over repeats (exact per-layer HLO cost).
        aux_acc = dict(zero_aux)
        pools, lanes = split(caches_in)
        collected = []
        for r in range(cfg.repeats):
            unit_params = [jax.tree.map(lambda a: a[r], t)
                           for t in params["units"]]
            unit_caches = (join(pools, [jax.tree.map(lambda a: a[r], c)
                                        for c in lanes])
                           if have_cache else None)
            x, new_caches, aux = unit_body(x, unit_params, unit_caches,
                                           jnp.int32(r))
            aux_acc = {k: aux_acc[k] + aux.get(k, 0) for k in aux_acc}
            pools, lanes_r = split(new_caches)
            collected.append(lanes_r)
        if want_cache and collected:
            new_unit_caches = join(pools, [
                jax.tree.map(lambda *leaves: jnp.stack(leaves),
                             *[collected[r][i] for r in range(cfg.repeats)])
                for i in range(len(cfg.unit))])
        else:
            new_unit_caches = None
    else:
        aux_acc = dict(zero_aux)
        new_unit_caches = None

    new_tail_caches = []
    for i, blk in enumerate(cfg.tail):
        c = cache["tail"][i] if have_cache else None
        tail_paged = attention.is_paged_cache(c)
        if tail_paged:             # a one-layer stack: a free reshape
            c = jax.tree.map(lambda a: a[None], c)
        x, nc, aux = block_apply(params["tail"][i], cfg, blk, x, pos,
                                 cache=c, decode=decode, context=ctx,
                                 settings=settings,
                                 block_tables=block_tables,
                                 layer=jnp.int32(0))
        if tail_paged:
            nc = jax.tree.map(lambda a: a[0], nc)
        new_tail_caches.append(nc)
        aux_acc = {k: aux_acc[k] + aux.get(k, 0) for k in aux_acc}

    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if logits_last_only and not decode:
        x = x[:, -1:]
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = layers.lm_head(head, cfg, x)

    new_cache = ({"units": new_unit_caches, "tail": new_tail_caches}
                 if want_cache else None)
    return logits, new_cache, aux_acc
