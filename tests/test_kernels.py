"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# XLA compiles dominate the runtime => slow tier
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(3)


def _attn_inputs(b, s, K, G, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, K, G, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, K, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, K, hd), dtype)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return q, k, v, pos


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("s,K,G,hd,window,chunk,qb,kb", [
    (128, 2, 2, 32, None, None, 64, 64),
    (128, 1, 4, 16, 48, None, 32, 32),
    (256, 2, 1, 64, None, 64, 64, 64),
    (64, 4, 1, 8, 16, None, 64, 64),     # single q block
])
def test_flash_attention_sweep(dtype, atol, s, K, G, hd, window, chunk, qb,
                               kb):
    q, k, v, pos = _attn_inputs(2, s, K, G, hd, dtype)
    out = ops.flash_attention(q, k, v, pos, pos, window=window, chunk=chunk,
                              backend="interpret", q_block=qb, kv_block=kb)
    exp = ref.flash_attention_ref(q, k, v, pos, pos, window=window,
                                  chunk=chunk)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("L,K,G,hd,window,kvb", [
    (128, 2, 2, 32, None, 32),
    (128, 1, 4, 16, 40, 64),
    (96, 8, 1, 8, None, 48),
])
def test_decode_attention_sweep(dtype, atol, L, K, G, hd, window, kvb):
    b = 3
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, K, G, hd), dtype)
    kc = jax.random.normal(ks[1], (b, L, K, hd), dtype)
    vc = jax.random.normal(ks[2], (b, L, K, hd), dtype)
    cpos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (b, L))
    positions = jnp.array([L - 1, L // 2, 7], jnp.int32)
    out = ops.decode_attention(q, kc, vc, cpos, positions, window=window,
                               backend="interpret", kv_block=kvb)
    exp = ref.decode_attention_ref(q, kc, vc, cpos, positions, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=atol,
                               rtol=atol)


def _stack_layers(leaf, layer, n_layers, seed):
    """A layer-stacked pool leaf holding `leaf` at `layer` and different
    values of the same dtype in every other layer."""
    rng = np.random.default_rng(seed)
    layers = []
    for i in range(n_layers):
        if i == layer:
            layers.append(np.asarray(leaf))
        elif leaf.dtype == jnp.int32:
            layers.append(rng.integers(-1, 64, leaf.shape).astype(np.int32))
        else:
            other = rng.normal(size=leaf.shape) * 64
            if leaf.dtype == jnp.int8:
                other = np.clip(other, -127, 127)
            layers.append(np.asarray(jnp.asarray(other).astype(leaf.dtype)))
    return jnp.asarray(np.stack(layers))


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("bs,K,G,hd,window", [
    (16, 2, 2, 32, None),
    (8, 1, 4, 16, 40),
    (32, 4, 1, 8, None),
])
@pytest.mark.parametrize("quant,stacked", [("none", False), ("none", True),
                                           ("int8", True)])
def test_paged_decode_attention_sweep(dtype, atol, bs, K, G, hd, window,
                                      quant, stacked):
    """The block-table kernel vs a dense ring reference: scatter each
    lane's blocks into a contiguous cache, run the plain decode oracle.
    Stacked cases read layer 1 of a 3-layer pool in place, and must equal
    the call on that layer's own pool (a one-layer stack) bit for bit."""
    from repro.models.attention import dequantize_kv, quantize_kv
    b, m_blocks, n_blocks = 3, 4, 9
    rng = np.random.default_rng(0)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, K, G, hd), dtype)
    kp = jax.random.normal(ks[1], (n_blocks, bs, K, hd), dtype)
    vp = jax.random.normal(ks[2], (n_blocks, bs, K, hd), dtype)
    kq, k_sc = quantize_kv(kp, quant)
    vq, v_sc = quantize_kv(vp, quant)
    # each lane owns a random disjoint set of blocks (0 is scratch);
    # lane i's positions run up to its decode cursor, later slots stay -1
    perm = rng.permutation(np.arange(1, n_blocks)).tolist()
    positions = np.array([3 * bs + bs // 2, bs - 1, 2 * bs], np.int32)
    tables = np.full((b, m_blocks), -1, np.int32)
    pool_pos = np.full((n_blocks, bs), -1, np.int32)
    for i in range(b):
        for j in range(-(-int(positions[i] + 1) // bs)):
            phys = perm.pop()
            tables[i, j] = phys
            for o in range(bs):
                if j * bs + o <= positions[i]:
                    pool_pos[phys, o] = j * bs + o
    leaves = (kq, vq, jnp.asarray(pool_pos), k_sc, v_sc)

    def call(leaves, layer):
        kp, vp, pp, ksc, vsc = leaves
        return ops.paged_decode_attention(
            q, kp, vp, pp, jnp.asarray(tables), jnp.asarray(positions),
            layer=layer, window=window, backend="interpret",
            k_scales=ksc, v_scales=vsc)

    out = call([None if x is None else x[None] for x in leaves], 0)
    if stacked:
        layer, n_layers = 1, 3
        st = [None if x is None else _stack_layers(x, layer, n_layers, i)
              for i, x in enumerate(leaves)]
        np.testing.assert_array_equal(
            np.asarray(call(st, jnp.int32(layer)), np.float32),
            np.asarray(out, np.float32))
    # dense reference: gather the blocks into [b, m_blocks*bs, ...]
    kd = dequantize_kv(kq, k_sc, quant, dtype)
    vd = dequantize_kv(vq, v_sc, quant, dtype)
    safe = np.where(tables >= 0, tables, 0)
    kd = jnp.asarray(np.asarray(kd)[safe].reshape(b, m_blocks * bs, K, hd))
    vd = jnp.asarray(np.asarray(vd)[safe].reshape(b, m_blocks * bs, K, hd))
    cpos = np.where(tables[..., None] >= 0, pool_pos[safe], -1)
    cpos = jnp.asarray(cpos.reshape(b, m_blocks * bs))
    exp = ref.decode_attention_ref(q, kd, vd, cpos, jnp.asarray(positions),
                                   window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("bs,K,G,hd,window", [
    (8, 2, 2, 16, None),
    (8, 1, 4, 16, 12),
])
def test_paged_decode_attention_trimmed_tables(dtype, atol, bs, K, G, hd,
                                               window):
    """The kernel's grid KV extent is the TABLE width: trimming tables to
    the blocks actually allocated (lane compaction does, per tick) must
    not change the output, and UNEVEN per-lane allocation (one lane deep,
    the rest shallow) must match the dense oracle at both widths."""
    b, m_blocks, n_blocks = 3, 6, 12
    rng = np.random.default_rng(3)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, K, G, hd), dtype)
    kp = jax.random.normal(ks[1], (n_blocks, bs, K, hd), dtype)
    vp = jax.random.normal(ks[2], (n_blocks, bs, K, hd), dtype)
    # lane 0 reaches 4 blocks; lanes 1-2 sit in their first block
    positions = np.array([3 * bs + 1, bs // 2, bs - 1], np.int32)
    perm = rng.permutation(np.arange(1, n_blocks)).tolist()
    tables = np.full((b, m_blocks), -1, np.int32)
    pool_pos = np.full((n_blocks, bs), -1, np.int32)
    for i in range(b):
        for j in range(-(-int(positions[i] + 1) // bs)):
            phys = perm.pop()
            tables[i, j] = phys
            for o in range(bs):
                if j * bs + o <= positions[i]:
                    pool_pos[phys, o] = j * bs + o
    # trimmed width = widest allocated row (4), well under m_blocks (6)
    trim = int((tables >= 0).sum(axis=1).max())
    assert trim < m_blocks
    full = ops.paged_decode_attention(
        q, kp[None], vp[None], jnp.asarray(pool_pos)[None],
        jnp.asarray(tables), jnp.asarray(positions), layer=0, window=window,
        backend="interpret")
    trimmed = ops.paged_decode_attention(
        q, kp[None], vp[None], jnp.asarray(pool_pos)[None],
        jnp.asarray(tables[:, :trim]), jnp.asarray(positions), layer=0,
        window=window, backend="interpret")
    np.testing.assert_allclose(np.asarray(trimmed, np.float32),
                               np.asarray(full, np.float32), atol=atol,
                               rtol=atol)
    safe = np.where(tables >= 0, tables, 0)
    kd = jnp.asarray(np.asarray(kp)[safe].reshape(b, m_blocks * bs, K, hd))
    vd = jnp.asarray(np.asarray(vp)[safe].reshape(b, m_blocks * bs, K, hd))
    cpos = np.where(tables[..., None] >= 0, pool_pos[safe], -1)
    cpos = jnp.asarray(cpos.reshape(b, m_blocks * bs))
    exp = ref.decode_attention_ref(q, kd, vd, cpos, jnp.asarray(positions),
                                   window=window)
    np.testing.assert_allclose(np.asarray(trimmed, np.float32),
                               np.asarray(exp, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("backend", ["interpret", "blocked"])
@pytest.mark.parametrize("s,h,dk,dv,chunk", [
    (128, 2, 16, 16, 32),
    (64, 1, 8, 24, 64),     # dk != dv
    (96, 4, 32, 32, 32),
])
def test_mlstm_scan_sweep(backend, s, h, dk, dv, chunk):
    b = 2
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, s, h, dk)) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, dk)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, dv))
    ig = jax.random.normal(ks[3], (b, s, h))
    fg = jax.random.normal(ks[4], (b, s, h)) + 2.0
    out, (C, n, m) = ops.mlstm_scan(q, k, v, ig, fg, chunk=chunk,
                                    backend=backend)
    exp, (Cr, nr, mr) = ops.mlstm_scan(q, k, v, ig, fg, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(Cr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=1e-5,
                               rtol=1e-5)


def test_mlstm_decode_step_matches_ref():
    b, h, dk, dv, S = 2, 2, 8, 8, 16
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, S, h, dk)) * 0.5
    k = jax.random.normal(ks[1], (b, S, h, dk)) * 0.5
    v = jax.random.normal(ks[2], (b, S, h, dv))
    ig = jax.random.normal(ks[3], (b, S, h))
    fg = jax.random.normal(ks[4], (b, S, h)) + 1.0
    exp, _ = ops.mlstm_scan(q, k, v, ig, fg, backend="ref")
    C = jnp.zeros((b, h, dk, dv))
    n = jnp.zeros((b, h, dk))
    m = jnp.full((b, h, 1), -jnp.inf)
    outs = []
    state = (C, n, m)
    for t in range(S):
        o, state = ops.mlstm_decode_step(q[:, t], k[:, t], v[:, t],
                                         ig[:, t], fg[:, t], state)
        outs.append(o)
    out = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4,
                               rtol=1e-4)


def test_flash_kernel_bf16_io_f32_math():
    """Kernel must not lose the online-softmax accuracy to bf16 accumulation."""
    q, k, v, pos = _attn_inputs(1, 128, 1, 1, 32, jnp.bfloat16)
    out = ops.flash_attention(q, k, v, pos, pos, backend="interpret",
                              q_block=32, kv_block=32)
    exp = ref.flash_attention_ref(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), pos, pos)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - exp))) < 0.03


@pytest.mark.parametrize("window,chunk", [(None, None), (48, None),
                                          (None, 64)])
def test_flash_attention_bwd_matches_ref_grads(window, chunk):
    """Pallas dQ/dK/dV kernels vs autodiff through the jnp oracle."""
    b, s, K, G, hd = 1, 128, 2, 2, 16
    q, k, v, pos = _attn_inputs(b, s, K, G, hd, jnp.float32)

    def loss_kernel(q, k, v):
        out = ops.flash_attention_trainable(
            q, k, v, pos, pos, window=window, chunk=chunk,
            q_block=32, kv_block=32, interpret=True)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        out = ref.flash_attention_ref(q, k, v, pos, pos, window=window,
                                      chunk=chunk)
        return jnp.sum(out * out)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_flash_fwd_lse_matches_ref():
    b, s, K, G, hd = 2, 64, 1, 2, 16
    q, k, v, pos = _attn_inputs(b, s, K, G, hd, jnp.float32)
    from repro.kernels.flash_attention import flash_attention_fwd
    qh = q.reshape(b, s, K * G, hd)
    out, lse = flash_attention_fwd(qh, k, v, pos, pos, q_block=32,
                                   kv_block=32, interpret=True,
                                   return_lse=True)
    # reference lse
    import numpy as _np
    scale = 1.0 / _np.sqrt(hd)
    s_ = jnp.einsum("bqkgh,bskh->bkgqs", q, k) * scale
    mask = (pos[:, None, :] <= pos[:, :, None])[:, None, None]
    s_ = jnp.where(jnp.moveaxis(mask, 3, 3), s_, -1e30)
    lse_ref = jax.scipy.special.logsumexp(s_, axis=-1)  # [b,K,G,s]
    lse_ref = jnp.moveaxis(lse_ref.reshape(b, K * G, s), 1, 2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused paged flash-prefill
# ---------------------------------------------------------------------------

def _prefill_case(seed, b, bs, m_blocks, n_blocks, C, K, G, hd, dtype,
                  quant, starts, n_valid):
    """Build one chunked-prefill scenario: per-lane history written through
    the jnp oracle writer (positions 0..starts[i]-1), block tables covering
    history + chunk, and a chunk at starts[i]..starts[i]+n_valid[i]-1 with
    trailing padding rows (-1). Returns (q, kn, vn, cache, tables, pos)."""
    from repro.models.attention import _paged_write_chunk, quantize_kv
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, C, K, G, hd), dtype)
    kn = jax.random.normal(ks[1], (b, C, K, hd), dtype)
    vn = jax.random.normal(ks[2], (b, C, K, hd), dtype)
    pool_dtype = {"none": dtype, "int8": jnp.int8, "int4": jnp.uint8}[quant]
    hd_s = hd // 2 if quant == "int4" else hd
    cache = {
        "kb": jnp.zeros((n_blocks, bs, K, hd_s), pool_dtype),
        "vb": jnp.zeros((n_blocks, bs, K, hd_s), pool_dtype),
        "pos": jnp.full((n_blocks, bs), -1, jnp.int32),
    }
    if quant != "none":
        cache["ks"] = jnp.zeros((n_blocks, bs, K), jnp.float32)
        cache["vs"] = jnp.zeros((n_blocks, bs, K), jnp.float32)
    # tables cover ceil((start + n_valid) / bs) logical blocks per lane,
    # drawn from a shared permutation of the non-scratch physical blocks
    perm = rng.permutation(np.arange(1, n_blocks)).tolist()
    tables = np.full((b, m_blocks), -1, np.int32)
    for i in range(b):
        need = -(-(starts[i] + n_valid[i]) // bs)
        for j in range(need):
            tables[i, j] = perm.pop()
    tables = jnp.asarray(tables)
    # history through the oracle writer: the kernel must merge ON TOP of
    # previously written (possibly quantized) blocks without disturbing
    # them
    H = max(starts)
    if H:
        kh = jax.random.normal(ks[3], (b, H, K, hd), dtype)
        vh = jax.random.normal(ks[4], (b, H, K, hd), dtype)
        hpos = jnp.asarray([[p if p < st else -1 for p in range(H)]
                            for st in starts], jnp.int32)
        cache = _one_layer(_paged_write_chunk(_stacked(cache), 0, tables,
                                              kh, vh, hpos))
    pos = jnp.asarray([[st + c if c < nv else -1 for c in range(C)]
                       for st, nv in zip(starts, n_valid)], jnp.int32)
    return q, kn, vn, cache, tables, pos


def _stacked(cache):
    return jax.tree.map(lambda a: a[None], cache)


def _one_layer(cache, layer=0):
    return jax.tree.map(lambda a: a[layer], cache)


def _run_chunk_append(q, kn, vn, cache, tables, pos, backend, window=None,
                      layer=None):
    """_chunk_append on a layer-stacked pool at `layer`; with no layer,
    on one layer's pool (stacked to one layer and back)."""
    from repro.configs.base import BlockSpec
    from repro.models import attention as A
    one = layer is None
    o, new = A._chunk_append(q, kn, vn, _stacked(cache) if one else cache,
                             BlockSpec(window=window), pos, tables,
                             A.AttnSettings(backend=backend),
                             layer=0 if one else layer)
    return o, _one_layer(new) if one else new


def _assert_pools_match(got, want, quant):
    """Pool leaves must agree EXCLUDING scratch block 0: the jnp oracle
    parks padding rows there while the kernel predicates the merge off —
    both are dead writes the mask can never surface. Codes and positions
    are bit-exact; the f32 scale stripes get 1-ULP slack because XLA may
    compile `max|x| / qmax` as a reciprocal multiply in one jit context
    and a true division in the other."""
    for key in ("kb", "vb", "pos"):
        np.testing.assert_array_equal(
            np.asarray(got[key])[1:].astype(np.float32),
            np.asarray(want[key])[1:].astype(np.float32), err_msg=key)
    if quant != "none":
        for key in ("ks", "vs"):
            np.testing.assert_allclose(np.asarray(got[key])[1:],
                                       np.asarray(want[key])[1:],
                                       rtol=1e-6, err_msg=key)


@pytest.mark.parametrize("dtype,quant,atol", [
    (jnp.float32, "none", 2e-5),
    (jnp.bfloat16, "none", 3e-2),
    (jnp.float32, "int8", 2e-2),
    (jnp.float32, "int4", 8e-2),
])
@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("stacked", [False, True])
def test_paged_prefill_attention_sweep(dtype, quant, atol, window, stacked):
    """The fused write+attend kernel vs the dense jnp oracle (scatter
    through the table, gather the virtual ring, dense SDPA): odd chunk
    lengths with padding rows, a fresh lane, multi-block chunks landing on
    half-full history blocks, and int8/int4 quantize-on-write — outputs
    agree and the pools agree EXACTLY (same codes, scales, positions).
    Stacked cases write and read layer 2 of a 3-layer pool in place: the
    same outputs and that layer's pool bit for bit as the call on the
    layer's own pool, and every other layer left untouched."""
    b, bs, m_blocks, n_blocks, C, K, G, hd = 3, 4, 6, 14, 7, 2, 2, 16
    starts, n_valid = (9, 0, 4), (5, 7, 3)   # odd + full + short chunks
    q, kn, vn, cache, tables, pos = _prefill_case(
        11, b, bs, m_blocks, n_blocks, C, K, G, hd, dtype, quant,
        starts, n_valid)
    o_ref, cache_ref = _run_chunk_append(q, kn, vn, cache, tables, pos,
                                         "naive", window=window)
    o_ker, cache_ker = _run_chunk_append(q, kn, vn, cache, tables, pos,
                                         "pallas", window=window)
    _assert_pools_match(cache_ker, cache_ref, quant)
    if stacked:
        layer, n_layers = 2, 3
        pool = {k: _stack_layers(v, layer, n_layers, i)
                for i, (k, v) in enumerate(sorted(cache.items()))}
        o_st, pool_st = _run_chunk_append(q, kn, vn, pool, tables, pos,
                                          "pallas", window=window,
                                          layer=jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(o_st, np.float32),
                                      np.asarray(o_ker, np.float32))
        for key in pool:
            got, was = np.asarray(pool_st[key]), np.asarray(pool[key])
            np.testing.assert_array_equal(got[layer],
                                          np.asarray(cache_ker[key]),
                                          err_msg=key)
            others = [i for i in range(n_layers) if i != layer]
            np.testing.assert_array_equal(got[others], was[others],
                                          err_msg=key)
    valid = np.asarray(pos) >= 0
    np.testing.assert_allclose(
        np.asarray(o_ker, np.float32)[valid],
        np.asarray(o_ref, np.float32)[valid], atol=atol, rtol=atol)


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_paged_prefill_attention_trimmed_tables(quant):
    """Like paged decode, the grid's KV extent is the table width: tables
    trimmed to the widest allocated row must produce the same outputs and
    the same pool as the full-width call."""
    b, bs, m_blocks, n_blocks, C, K, G, hd = 3, 4, 8, 14, 4, 1, 4, 16
    starts, n_valid = (8, 0, 4), (4, 4, 4)
    q, kn, vn, cache, tables, pos = _prefill_case(
        5, b, bs, m_blocks, n_blocks, C, K, G, hd, jnp.float32, quant,
        starts, n_valid)
    trim = int((np.asarray(tables) >= 0).sum(axis=1).max())
    assert trim < m_blocks
    o_full, cache_full = _run_chunk_append(q, kn, vn, cache, tables, pos,
                                           "pallas")
    o_trim, cache_trim = _run_chunk_append(q, kn, vn, cache,
                                           tables[:, :trim], pos, "pallas")
    _assert_pools_match(cache_trim, cache_full, quant)
    np.testing.assert_allclose(np.asarray(o_trim, np.float32),
                               np.asarray(o_full, np.float32),
                               atol=2e-5, rtol=2e-5)
    o_ref, _ = _run_chunk_append(q, kn, vn, cache, tables, pos, "naive")
    atol = 2e-5 if quant == "none" else 2e-2
    np.testing.assert_allclose(np.asarray(o_trim, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=atol, rtol=atol)
