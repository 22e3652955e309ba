"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode cannot see what the TPU compiler refuses (block shapes off
the (8, 128) tiling, unsupported in-kernel layouts), so each kernel is
lowered and compiled here for a described v5e chip at mistral-nemo-12b's
head shape: 32 query heads over 8 KV heads, head_dim 128, 16-position KV
blocks, in a pool stacked over 3 layers. Nothing runs; a compile takes
about two seconds.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import paged_decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.prefill_attention import paged_prefill_attention_fwd

K, G, HD, BS = 8, 4, 128, 16          # kv heads, q per kv head, head_dim
N_BLOCKS, LANES, TABLE, CHUNK, LAYERS = 64, 4, 8, 64, 3
POOL_DTYPE = {"none": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _pool(quant):
    shapes = [((LAYERS, N_BLOCKS, BS, K, HD), POOL_DTYPE[quant])] * 2
    scales = [((LAYERS, N_BLOCKS, BS, K), jnp.float32)] * 2
    return shapes, scales if quant != "none" else []


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_paged_decode_compiles_for_v5e(chip, quant):
    pools, scales = _pool(quant)

    def step(q, kp, vp, pos, tables, cursor, layer, *sc):
        ks, vs = sc or (None, None)
        return paged_decode_attention_fwd(q, kp, vp, pos, tables, cursor,
                                          layer=layer, k_scales=ks,
                                          v_scales=vs)

    _compile(step, chip, ((LANES, K, G, HD), jnp.bfloat16), *pools,
             ((LAYERS, N_BLOCKS, BS), jnp.int32), ((LANES, TABLE), jnp.int32),
             ((LANES,), jnp.int32), ((), jnp.int32), *scales)


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_paged_prefill_compiles_for_v5e(chip, quant):
    pools, scales = _pool(quant)

    def step(q, kn, vn, kp, vp, pos, tables, qpos, layer, *sc):
        ks, vs = sc or (None, None)
        return paged_prefill_attention_fwd(q, kn, vn, kp, vp, pos, tables,
                                           qpos, layer=layer, k_scales=ks,
                                           v_scales=vs)

    new = ((LANES, CHUNK, K, HD), jnp.bfloat16)
    _compile(step, chip, ((LANES, CHUNK, K, G, HD), jnp.bfloat16), new, new,
             *pools, ((LAYERS, N_BLOCKS, BS), jnp.int32),
             ((LANES, TABLE), jnp.int32), ((LANES, CHUNK), jnp.int32),
             ((), jnp.int32), *scales)


@pytest.mark.parametrize("s", [384, 1024])
def test_flash_forward_compiles_for_v5e(chip, s):
    kv = ((2, s, K, HD), jnp.bfloat16)
    _compile(flash_attention_fwd, chip, ((2, s, K * G, HD), jnp.bfloat16),
             kv, kv, ((2, s), jnp.int32), ((2, s), jnp.int32))


def _pool_moves(text, shapes):
    """The copies, dynamic slices and dynamic update slices of compiled HLO
    `text` whose result has one of `shapes` (as "bf16[...]"): as ops, and
    as fusions that hold any of the three."""
    moves_ops = ("copy(", "dynamic-slice(", "dynamic-update-slice(")
    bodies, name = {}, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            bodies[name.lstrip("%")] = []
        elif name is not None:
            bodies[name.lstrip("%")].append(line)
    found = []
    for line in text.splitlines():
        if " = " not in line:
            continue
        result = line.split(" = ", 1)[1]
        if not any(result.startswith(s + "{") or result.startswith(s + " ")
                   for s in shapes):
            continue
        op = result.split("{", 1)[1].split(" ", 1)[1] if "{" in \
            result.split(" ", 1)[0] else result.split(" ", 1)[1]
        if op.startswith(moves_ops):
            found.append(line.strip())
        elif op.startswith("fusion(") and "calls=" in op:
            called = op.split("calls=", 1)[1].split(",")[0].split()[0]
            body = "\n".join(bodies.get(called.lstrip("%"), []))
            if any(" " + m in body for m in moves_ops):
                found.append(line.strip())
    return found


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_paged_steps_keep_the_pool_in_place(chip, monkeypatch, kind):
    """The compact decode and chunk prefill steps of a 3-layer model with
    Mistral's attention shape, compiled for a v5e with the Pallas kernels,
    neither copy the layer-stacked KV pool nor slice a layer's pool out of
    it or stack one back: the layer scan carries the pool and each layer
    writes its blocks in place."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import depth_variant
    from repro.kernels import ops as kops
    from repro.models import attention as A
    from repro.models import init_params
    from repro.models import model as M
    from repro.runtime import serve_step as SS
    # the CPU process would lower the kernels in interpret mode
    monkeypatch.setattr(kops, "_interpret", lambda backend, path: False)
    cfg = dataclasses.replace(
        depth_variant(get_config("mistral-nemo-12b").reduced(), 3),
        d_model=256, n_heads=K * G, n_kv_heads=K, head_dim=HD)
    lanes, n_blocks, block, context, chunk = 4, 16, 128, 512, 128
    settings = M.ModelSettings(attn=A.AttnSettings(backend="pallas"))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pool = SS.init_paged_pool(cfg, lanes, n_blocks, block, context,
                              abstract=True)
    place = lambda t: jax.tree.map(          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=chip)
    width = 1 if kind == "decode" else chunk
    make = (SS.make_compact_decode_step if kind == "decode"
            else SS.make_chunk_prefill_step)
    step = jax.jit(make(cfg, settings), static_argnames=("context",),
                   donate_argnums=(5,))
    text = step.lower(place(params), i32(lanes, width),
                      *((i32(lanes),) if kind == "decode"
                        else (i32(lanes, width),)),
                      i32(lanes, context // block), i32(lanes), place(pool),
                      context=context).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 1
    leaf = (n_blocks, block, K, HD)
    shapes = ["bf16[%s]" % ",".join(map(str, s))
              for s in ((cfg.repeats,) + leaf, (1,) + leaf, leaf)]
    assert _pool_moves(text, shapes) == []
