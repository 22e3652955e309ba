"""Plain float32 reference of a Mistral-style decoder, and its seeded weights.

The block follows the published Mistral description (hf:mistralai/
Mistral-Nemo-Base-2407, `MistralForCausalLM`): RMSNorm before attention and
before the MLP, grouped-query attention with rotary embeddings (the
half-split `rotate_half` layout, query head h reads key/value head h // G),
a SwiGLU MLP, a final RMSNorm and an untied LM head. The token embedding
enters unscaled, as published, unless the configuration file states an
`embedding_multiplier` (a departure from the source, listed in its
`reduced`).

Everything runs in float32 at `Precision.HIGHEST`, one layer at a time, so a
published-width model fits one chip: each layer's weights are drawn from the
seed, used and dropped. The same module draws the weights the system under
test is served with (`leaf`), so both sides read identical bf16 values and
nothing is taken from the program. Nothing here imports the program.

`precision="fp8"` is the control: every projection, the MLP and the LM head
take their inputs and weights rounded to float8 e4m3 (per-row and per-output-
column absmax scales), the step below the bf16 the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                    # largest finite float8_e4m3fn

# leaf ids under one layer's key (weights draw from fold_in(layer key, id))
ATTN_NORM, WQ, WK, WV, WO, MLP_NORM, WI, WO_MLP = range(8)
EMBED, HEAD, FINAL_NORM = range(3)
LAYER_BASE = 1000


def dims(c: Dict) -> Dict[str, int]:
    """The sizes the reference needs, read from a configuration file's
    Hugging Face keys."""
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    return dict(d=c["hidden_size"], f=c["intermediate_size"], H=H, K=K,
                G=H // K, hd=c["head_dim"], V=c["vocab_size"],
                L=c["num_hidden_layers"])


def root_key(seed: int):
    """A PRNG key for any whole seed below 2**64 (more than 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_shapes(c: Dict) -> Dict[int, Tuple[int, ...]]:
    """Shape of each per-layer leaf."""
    m = dims(c)
    d, hq, hk = m["d"], m["H"] * m["hd"], m["K"] * m["hd"]
    return {ATTN_NORM: (d,), WQ: (d, hq), WK: (d, hk), WV: (d, hk),
            WO: (hq, d), MLP_NORM: (d,), WI: (d, 2 * m["f"]),
            WO_MLP: (m["f"], d)}


def leaf(key, shape, std: float):
    """One weight leaf: a normal draw scaled by `std`, in the bf16 the
    configuration serves. Norm leaves are the deviation s of the RMSNorm
    weight 1 + s."""
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def layer_key(key, layer: int, leaf_id: int):
    return jax.random.fold_in(jax.random.fold_in(key, LAYER_BASE + layer),
                              leaf_id)


def global_key(key, leaf_id: int):
    return jax.random.fold_in(key, leaf_id)


def std_of(c: Dict, is_norm: bool) -> float:
    w = c["weights"]
    return w["norm_std"] if is_norm else w["std"]


@functools.partial(jax.jit, static_argnums=(2,))
def _layer_weights(key, layer, cfg_items: Tuple):
    c = _unfreeze(cfg_items)
    out = {}
    for lid, shape in leaf_shapes(c).items():
        norm = lid in (ATTN_NORM, MLP_NORM)
        out[lid] = leaf(layer_key(key, layer, lid), shape,
                        std_of(c, norm)).astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _global_weight(key, leaf_id: int, cfg_items: Tuple):
    c = _unfreeze(cfg_items)
    m = dims(c)
    shape = {EMBED: (m["V"], m["d"]), HEAD: (m["V"], m["d"]),
             FINAL_NORM: (m["d"],)}[leaf_id]
    return leaf(global_key(key, leaf_id), shape,
                std_of(c, leaf_id == FINAL_NORM)).astype(jnp.float32)


def freeze(c: Dict) -> Tuple:
    """A hashable copy of the keys the reference reads (jit static arg)."""
    keep = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size",
            "num_hidden_layers", "rms_norm_eps", "rope_theta")
    w = c["weights"]
    return tuple((k, c[k]) for k in keep) + (
        ("weights", (("std", w["std"]), ("norm_std", w["norm_std"]))),)


def _unfreeze(items: Tuple) -> Dict:
    c = dict(items)
    c["weights"] = dict(c["weights"])
    return c


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _round_fp8(x, axis):
    """x rounded to float8 e4m3 with an absmax scale along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """a [..., n] @ w [n, m] in float32 at HIGHEST; the control rounds a per
    row and w per output column to float8 first."""
    if fp8:
        a = _round_fp8(a, -1)
        w = _round_fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rmsnorm(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def _rope(x, pos, theta):
    """x [T, h, hd]; pos [T]; half-split rotation (`rotate_half`)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


QBLOCK = 512


@functools.partial(jax.jit, static_argnums=(4, 5))
def _layer(x, w, pos, seg, cfg_items: Tuple, fp8: bool):
    """One decoder layer over packed sequences: x [T, d]; pos [T] position
    within its sequence; seg [T] sequence id (-1 = padding). Attention is
    causal within a sequence, computed a block of QBLOCK queries at a time."""
    c = _unfreeze(cfg_items)
    m = dims(c)
    T = x.shape[0]
    H, K, G, hd = m["H"], m["K"], m["G"], m["hd"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rmsnorm(x, w[ATTN_NORM], eps)
    q = _rope(_mm(h, w[WQ], fp8).reshape(T, H, hd), pos, theta)
    k = _rope(_mm(h, w[WK], fp8).reshape(T, K, hd), pos, theta)
    v = _mm(h, w[WV], fp8).reshape(T, K, hd)
    k = jnp.repeat(k, G, axis=1)                   # query head h -> h // G
    v = jnp.repeat(v, G, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        pb = jax.lax.dynamic_slice_in_dim(pos, i * QBLOCK, QBLOCK, 0)
        sb = jax.lax.dynamic_slice_in_dim(seg, i * QBLOCK, QBLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        mask = ((sb[:, None] == seg[None, :]) & (sb[:, None] >= 0)
                & (pos[None, :] <= pb[:, None]))
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(mask[None], p, 0.0)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(T // QBLOCK)).reshape(T, H * hd)
    x = x + _mm(o, w[WO], fp8)
    h = _rmsnorm(x, w[MLP_NORM], eps)
    gu = _mm(h, w[WI], fp8)
    gate, up = gu[:, :m["f"]], gu[:, m["f"]:]
    return x + _mm(jax.nn.silu(gate) * up, w[WO_MLP], fp8)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, scale: float):
    return table[tokens] * scale


@functools.partial(jax.jit, static_argnums=(3,))
def _final(x, rows, s, eps: float):
    return _rmsnorm(x[rows], s, eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _head_stats(h, head, fp8: bool):
    """Per row of h [R, d]: (max logit, std of the logits, argmax)."""
    logits = _mm(h, head.T, fp8)
    return (jnp.max(logits, -1), jnp.std(logits, -1),
            jnp.argmax(logits, -1).astype(jnp.int32))


@jax.jit
def _logit_at(h, head, tokens):
    return jnp.sum(h * head[tokens], axis=-1, dtype=jnp.float32)


def pack(seqs: Sequence[Sequence[int]], total: int = 0):
    """Concatenate token sequences, padded to `total` positions (at least a
    multiple of QBLOCK that holds them all). Returns (tokens [T], positions
    [T], segment ids [T], start offset of each)."""
    n = sum(len(s) for s in seqs)
    T = max(-(-n // QBLOCK) * QBLOCK, total)
    tok = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    starts, off = [], 0
    for i, s in enumerate(seqs):
        tok[off:off + len(s)] = s
        pos[off:off + len(s)] = np.arange(len(s))
        seg[off:off + len(s)] = i
        starts.append(off)
        off += len(s)
    return tok, pos, seg, starts


HEAD_ROWS = 256


def final_hidden(c: Dict, seed: int, seqs: Sequence[Sequence[int]],
                 rows: Sequence[int], precision: str = "f32",
                 total: int = 0):
    """The final-norm hidden state [len(rows), d] at packed row indices
    `rows` of `seqs` (packed into `total` positions), computed layer by
    layer with each layer's weights drawn, used and dropped."""
    fp8 = precision == "fp8"
    items = freeze(c)
    key = root_key(seed)
    m = dims(c)
    tok, pos, seg, _ = pack(seqs, total)
    emb = _global_weight(key, EMBED, items)
    x = _embed(emb, jnp.asarray(tok), float(c.get("embedding_multiplier",
                                                  1.0)))
    del emb
    pos_d, seg_d = jnp.asarray(pos), jnp.asarray(seg)
    for layer in range(m["L"]):
        w = _layer_weights(key, jnp.int32(layer), items)
        x = _layer(x, w, pos_d, seg_d, items, fp8)
        del w
    s = _global_weight(key, FINAL_NORM, items)
    h = _final(x, jnp.asarray(np.asarray(rows, np.int32)), s,
               float(c["rms_norm_eps"]))
    return h


def head_scores(c: Dict, seed: int, h, tokens_at: Sequence[np.ndarray],
                precision: str = "f32"):
    """For final hidden rows h [R, d]: per row the max logit, the logits'
    std, the argmax, and the logit of each token array in `tokens_at`
    (each [R]). The head is applied HEAD_ROWS rows at a time."""
    fp8 = precision == "fp8"
    head = _global_weight(root_key(seed), HEAD, freeze(c))
    R = h.shape[0]
    out = {"max": [], "std": [], "argmax": [],
           "at": [[] for _ in tokens_at]}
    for r0 in range(0, R, HEAD_ROWS):
        hb = h[r0:r0 + HEAD_ROWS]
        pad = HEAD_ROWS - hb.shape[0]
        if pad:
            hb = jnp.pad(hb, ((0, pad), (0, 0)))
        mx, sd, am = _head_stats(hb, head, fp8)
        n = HEAD_ROWS - pad
        out["max"].append(np.asarray(mx)[:n])
        out["std"].append(np.asarray(sd)[:n])
        out["argmax"].append(np.asarray(am)[:n])
        for j, t in enumerate(tokens_at):
            tb = np.zeros(HEAD_ROWS, np.int32)
            tb[:n] = t[r0:r0 + n]
            out["at"][j].append(np.asarray(
                _logit_at(hb, head, jnp.asarray(tb)))[:n])
    del head
    res = {k: np.concatenate(out[k]) for k in ("max", "std", "argmax")}
    res["at"] = [np.concatenate(a) for a in out["at"]]
    return res


def token_flops(c: Dict, context: int, head: bool) -> float:
    """Model operations for one token that attends `context` positions:
    every projection and MLP matmul of every layer (2 flops per weight),
    attention's QK^T and PV (4 * H * hd per attended position per layer),
    and the LM head when its logits are needed. The embedding is a lookup
    and counts nothing."""
    m = dims(c)
    d, hq, hk = m["d"], m["H"] * m["hd"], m["K"] * m["hd"]
    per_layer = d * hq + 2 * d * hk + hq * d + 3 * d * m["f"]
    f = 2.0 * per_layer * m["L"] + 4.0 * m["L"] * hq * context
    return f + (2.0 * m["V"] * d if head else 0.0)


def served_rows(prompts: List[Sequence[int]], served: List[Sequence[int]]):
    """Teacher-forced sequences (prompt + served tokens but the last) and,
    for each served token, the packed row whose logits predicted it."""
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    _, _, _, starts = pack(seqs)
    rows, targets = [], []
    for st, p, s in zip(starts, prompts, served):
        for j, t in enumerate(s):
            rows.append(st + len(p) - 1 + j)
            targets.append(int(t))
    return seqs, np.asarray(rows, np.int32), np.asarray(targets, np.int32)
