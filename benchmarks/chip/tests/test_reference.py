"""The float32 reference against the program's model code, at small widths.

The reference imports nothing of the program; this test does, to show that
both compute the same function of the same seeded weights.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapters import mistral as A
from references import mistral as R

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(CONFIGS, "nemo12b-d12.json")) as f:
        c = json.load(f)
    return {**c, **c["rehearsal"]}


def reference_logits(c, seed, seqs, precision="f32"):
    tok, _, _, starts = R.pack(seqs)
    rows = [st + i for st, s in zip(starts, seqs) for i in range(len(s))]
    h = R.final_hidden(c, seed, seqs, rows, precision=precision)
    head = R._global_weight(R.root_key(seed), R.HEAD, R.freeze(c))
    if precision == "fp8":
        return np.asarray(R._mm(h, head.T, True))
    return np.asarray(jnp.matmul(h, head.T, precision=R.HIGHEST))


def program_logits(c, seed, seqs):
    from repro.models import model as M
    cfg = dataclasses.replace(A.program_config(c), param_dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          A.program_params(c, seed, None))
    out = []
    with jax.default_matmul_precision("highest"):
        for s in seqs:
            logits, _, _ = M.apply(params, cfg, jnp.asarray([s], jnp.int32))
            out.append(np.asarray(logits[0]))
    return np.concatenate(out)


def test_reference_matches_the_model_code(small):
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, small["vocab_size"], n).tolist()
            for n in (37, 300, 5)]
    ref = reference_logits(small, 2**32 + 11, seqs)
    prog = program_logits(small, 2**32 + 11, seqs)
    err = np.max(np.abs(ref - prog)) / np.max(np.abs(ref))
    assert err < 1e-4, err


def test_control_is_lower_precision(small):
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, small["vocab_size"], 200).tolist()]
    ref = reference_logits(small, 5, seqs)
    ctl = reference_logits(small, 5, seqs, precision="fp8")
    rel = np.max(np.abs(ref - ctl)) / np.max(np.abs(ref))
    assert 1e-3 < rel < 0.5, rel


def test_stacked_weights_equal_per_layer_draws(small):
    tree = A.program_params(small, 77, None)
    per = R._layer_weights(R.root_key(77), jnp.int32(1), R.freeze(small))
    stacked = tree["units"][0]["mlp"]["wi"][1].astype(jnp.float32)
    assert np.array_equal(np.asarray(stacked), np.asarray(per[R.WI]))
    wq = tree["units"][0]["mixer"]["wq"][0].astype(jnp.float32)
    per0 = R._layer_weights(R.root_key(77), jnp.int32(0), R.freeze(small))
    assert np.array_equal(np.asarray(wq), np.asarray(per0[R.WQ]))


def test_tree_matches_the_program_layout(small):
    from repro.models import init_params
    cfg = A.program_config(small)
    want = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = A.abstract_params(small)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (w.shape, w.dtype) == (g.shape, g.dtype)
