"""The system under test, built from a configuration file.

Maps the published config keys onto the program's ``ModelConfig`` and the
file's ``serve`` group onto its ``ServeConfig``, and lays the seeded weights
of ``bench/weights.py`` out as the program's parameter tree, in one jitted
call on the device.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.weights import Shapes, global_weights, layer_weights, seed_key
from repro.config.model import ModelConfig
from repro.config.run import ServeConfig


def model_config(name: str, c: Dict[str, Any]) -> ModelConfig:
    s = Shapes.from_config(c)
    return ModelConfig(
        arch_id=name, family="dense", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.ff, vocab_size=s.vocab, mlp_kind="swiglu",
        rope_theta=s.rope_theta, tie_embeddings=s.tied, norm_eps=s.eps,
        dtype=s.dtype)


def serve_config(c: Dict[str, Any], **over) -> ServeConfig:
    kw = dict(c["serve"], temperature=0.0, eos_id=-1)
    kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
    kw.update(over)
    return ServeConfig(**kw)


def _program_params(key, s: Shapes):
    dt = jnp.dtype(s.dtype)
    L, d, n = s.layers, s.d, s.head_dim

    def stacked(leaf, shape):
        return jax.vmap(
            lambda l: layer_weights(key, s, l)[leaf].astype(dt).reshape(shape)
        )(jnp.arange(L))

    g = global_weights(key, s)
    params = {
        "embed": g["embed"].astype(dt),
        "final_norm": {"scale": g["final_norm"].astype(dt)},
        "layers": {"0": {
            "norm1": {"scale": stacked("attn_norm", (d,))},
            "norm2": {"scale": stacked("mlp_norm", (d,))},
            "mixer": {
                "wq": stacked("wq", (d, s.heads, n)),
                "wk": stacked("wk", (d, s.kv_heads, n)),
                "wv": stacked("wv", (d, s.kv_heads, n)),
                "wo": stacked("wo", (s.heads, n, d)),
            },
            "mlp": {"wg": stacked("w_gate", (d, s.ff)),
                    "wi": stacked("w_up", (d, s.ff)),
                    "wo": stacked("w_down", (s.ff, d))},
        }},
        "tail": {},
    }
    if not s.tied:
        params["unembed"] = g["unembed"].astype(dt)
    return params


@functools.lru_cache(maxsize=None)
def _params_program(s: Shapes):
    return jax.jit(functools.partial(_program_params, s=s))


def make_params(seed: int, s: Shapes):
    """The program's parameter tree for ``seed``, made on the default device
    in one jitted call."""
    return _params_program(s)(seed_key(seed))
