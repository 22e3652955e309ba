"""How a Mistral configuration file becomes the system under test's inputs.

`program_config` maps the file's Hugging Face keys onto the program's
ModelConfig; `program_params` draws the weights with the reference's own
`leaf` (same keys, same bf16 values) into the program's parameter tree, in
one jitted call placed straight into the given shardings.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from references import mistral as R


def program_config(c: Dict):
    from repro.configs.base import ATTN, MLP_DENSE, BlockSpec, ModelConfig
    m = R.dims(c)
    return ModelConfig(
        name=c["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], head_dim=m["hd"], d_ff=m["f"],
        vocab_size=m["V"],
        unit=(BlockSpec(mixer=ATTN, mlp=MLP_DENSE, window=None),),
        activation="swiglu", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), param_dtype="bfloat16")


def _tree(c: Dict, key):
    """The program's parameter tree for a dense attention + SwiGLU stack:
    per-layer leaves stacked on a leading layer axis (the program scans
    over it)."""
    m = R.dims(c)
    layers = jnp.arange(m["L"])
    shapes = R.leaf_shapes(c)

    def stacked(lid, norm):
        def one(layer):
            return R.leaf(R.layer_key(key, layer, lid), shapes[lid],
                          R.std_of(c, norm))
        return jax.vmap(one)(layers)

    def glob(lid, shape, norm=False):
        return R.leaf(R.global_key(key, lid), shape, R.std_of(c, norm))

    return {
        "embed": {"table": glob(R.EMBED, (m["V"], m["d"]))},
        "units": [{
            "mixer": {"norm": stacked(R.ATTN_NORM, True),
                      "wq": stacked(R.WQ, False), "wk": stacked(R.WK, False),
                      "wv": stacked(R.WV, False), "wo": stacked(R.WO, False)},
            "mlp": {"norm": stacked(R.MLP_NORM, True),
                    "wi": stacked(R.WI, False),
                    "wo": stacked(R.WO_MLP, False)},
        }],
        "tail": [],
        "final_norm": glob(R.FINAL_NORM, (m["d"],), norm=True),
        "head": {"table": glob(R.HEAD, (m["V"], m["d"]))},
    }


def program_params(c: Dict, seed: int, shardings):
    """The served weights, made on the device in one jitted call."""
    return jax.jit(lambda k: _tree(c, k), out_shardings=shardings)(
        R.root_key(seed))


def abstract_params(c: Dict):
    return jax.eval_shape(lambda k: _tree(c, k), R.root_key(0))
