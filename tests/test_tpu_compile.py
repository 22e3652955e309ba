"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode cannot see what the TPU compiler refuses (block shapes off
the (8, 128) tiling, unsupported in-kernel layouts), so each kernel is
lowered and compiled here for a described v5e chip at mistral-nemo-12b's
head shape: 32 query heads over 8 KV heads, head_dim 128, 16-position KV
blocks. Nothing runs; a compile takes about two seconds.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import paged_decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.prefill_attention import paged_prefill_attention_fwd

K, G, HD, BS = 8, 4, 128, 16          # kv heads, q per kv head, head_dim
N_BLOCKS, LANES, TABLE, CHUNK = 64, 4, 8, 64
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
    shapes = [((N_BLOCKS, BS, K, HD), POOL_DTYPE[quant])] * 2
    scales = [((N_BLOCKS, BS, K), jnp.float32)] * 2
    return shapes, scales if quant != "none" else []


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_paged_decode_compiles_for_v5e(chip, quant):
    pools, scales = _pool(quant)

    def step(q, kp, vp, pos, tables, cursor, *sc):
        ks, vs = sc or (None, None)
        return paged_decode_attention_fwd(q, kp, vp, pos, tables, cursor,
                                          k_scales=ks, v_scales=vs)

    _compile(step, chip, ((LANES, K, G, HD), jnp.bfloat16), *pools,
             ((N_BLOCKS, BS), jnp.int32), ((LANES, TABLE), jnp.int32),
             ((LANES,), jnp.int32), *scales)


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_paged_prefill_compiles_for_v5e(chip, quant):
    pools, scales = _pool(quant)

    def step(q, kn, vn, kp, vp, pos, tables, qpos, *sc):
        ks, vs = sc or (None, None)
        return paged_prefill_attention_fwd(q, kn, vn, kp, vp, pos, tables,
                                           qpos, k_scales=ks, v_scales=vs)

    new = ((LANES, CHUNK, K, HD), jnp.bfloat16)
    _compile(step, chip, ((LANES, CHUNK, K, G, HD), jnp.bfloat16), new, new,
             *pools, ((N_BLOCKS, BS), jnp.int32), ((LANES, TABLE), jnp.int32),
             ((LANES, CHUNK), jnp.int32), *scales)


@pytest.mark.parametrize("s", [384, 1024])
def test_flash_forward_compiles_for_v5e(chip, s):
    kv = ((2, s, K, HD), jnp.bfloat16)
    _compile(flash_attention_fwd, chip, ((2, s, K * G, HD), jnp.bfloat16),
             kv, kv, ((2, s), jnp.int32), ((2, s), jnp.int32))
