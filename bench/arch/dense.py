"""The dense GQA decoder (Llama, Mistral): its sizes from the published
``config.json`` keys, seeded weights, the program's configuration and
parameter tree, the reference's layer (``dense_block`` of
``bench/reference/decoder.py``), and the work that serving it needs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import decoder
from bench.weights import normal, seed_key, served
from repro.config.model import ModelConfig

MODEL_TYPES = ("llama", "mistral")

# Keys whose presence with another value would change the equations written
# in bench/reference; a configuration that sets them is refused.
_UNSUPPORTED = {"rope_scaling": None, "attention_bias": False,
                "sliding_window": None, "hidden_act": "silu"}

# Leaf -> stream id; a weight's values depend on the seed, its id and its
# layer only.
LEAF_IDS = {"embed": 1, "unembed": 2, "final_norm": 3, "attn_norm": 10,
            "wq": 11, "wk": 12, "wv": 13, "wo": 14, "mlp_norm": 15,
            "w_gate": 16, "w_up": 17, "w_down": 18}


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    dtype: str

    def layer_params(self) -> int:
        d, n = self.d, self.head_dim
        attn = d * n * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * d * self.ff + 2 * d

    def head_params(self) -> int:
        """Parameters of the output head (the embedding when tied)."""
        return self.d * self.vocab

    def kv_bytes_per_token(self) -> int:
        """K and V of one token over every layer, in the served dtype."""
        item = jnp.dtype(self.dtype).itemsize
        return 2 * self.layers * self.kv_heads * self.head_dim * item


def shapes(c: Dict[str, Any]) -> Shapes:
    """The sizes of configuration ``c``; raises ValueError for a key whose
    value the equations here do not cover."""
    for k, ok in _UNSUPPORTED.items():
        if c.get(k, ok) != ok:
            raise ValueError(f"{k}={c[k]!r} is not covered by the "
                             "reference's equations")
    heads = int(c["num_attention_heads"])
    return Shapes(layers=int(c["num_hidden_layers"]),
                  d=int(c["hidden_size"]), heads=heads,
                  kv_heads=int(c["num_key_value_heads"]),
                  head_dim=int(c.get("head_dim")
                               or c["hidden_size"] // heads),
                  ff=int(c["intermediate_size"]),
                  vocab=int(c["vocab_size"]),
                  rope_theta=float(c["rope_theta"]),
                  eps=float(c["rms_norm_eps"]),
                  tied=bool(c["tie_word_embeddings"]),
                  dtype=str(c.get("dtype", "bfloat16")))


def _normal(key, leaf: str, layer, shape, std: float) -> jax.Array:
    return normal(key, LEAF_IDS[leaf], layer, shape, std)


def layer_weights(key, s: Shapes, layer) -> Dict[str, jax.Array]:
    """Float32 weights of one layer (``layer`` may be traced), in the
    checkpoint's orientation: projections are (in, out)."""
    d, n, f = s.d, s.head_dim, s.ff
    hq, hk = s.heads * n, s.kv_heads * n
    return {
        "attn_norm": 1.0 + _normal(key, "attn_norm", layer, (d,), 0.1),
        "wq": _normal(key, "wq", layer, (d, hq), d ** -0.5),
        "wk": _normal(key, "wk", layer, (d, hk), d ** -0.5),
        "wv": _normal(key, "wv", layer, (d, hk), d ** -0.5),
        "wo": _normal(key, "wo", layer, (hq, d), hq ** -0.5),
        "mlp_norm": 1.0 + _normal(key, "mlp_norm", layer, (d,), 0.1),
        "w_gate": _normal(key, "w_gate", layer, (d, f), d ** -0.5),
        "w_up": _normal(key, "w_up", layer, (d, f), d ** -0.5),
        "w_down": _normal(key, "w_down", layer, (f, d), f ** -0.5),
    }


def global_weights(key, s: Shapes) -> Dict[str, jax.Array]:
    """Float32 embedding, final norm and (untied) output head (d, vocab)."""
    out = {"embed": _normal(key, "embed", 0, (s.vocab, s.d), 0.02),
           "final_norm": 1.0 + _normal(key, "final_norm", 0, (s.d,), 0.1)}
    if not s.tied:
        out["unembed"] = _normal(key, "unembed", 0, (s.d, s.vocab),
                                 s.d ** -0.5)
    return out


def model_config(name: str, c: Dict[str, Any]) -> ModelConfig:
    """The program's ``ModelConfig`` for configuration ``c``."""
    s = shapes(c)
    return ModelConfig(
        arch_id=name, family="dense", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.ff, vocab_size=s.vocab, mlp_kind="swiglu",
        rope_theta=s.rope_theta, tie_embeddings=s.tied, norm_eps=s.eps,
        dtype=s.dtype)


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


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def reference_layer(x, key, layer, s: Shapes, fp8: bool):
    """Layer ``layer`` of the float32 reference, its weights remade from
    ``key`` and rounded as served."""
    return decoder.dense_block(x, served(layer_weights(key, s, layer), s),
                               s, fp8)


def served_gaps(seed: int, s: Shapes, requests, control: bool = False):
    """``bench/reference/decoder.py``'s ``served_gaps`` with this
    architecture's layer and weights; ``control`` is the fp8 control."""
    return decoder.served_gaps(seed, s, requests, reference_layer,
                               global_weights, control)


# Operations and bytes that serving needs, from the model's shapes.
#
# These count the work the algorithm needs, not what one implementation
# happens to move: a decode step reads every weight once and the K/V of the
# live context of each active row, however attention is implemented.  So a
# share of a roofline computed from them cannot pass 100%, and stays the
# same yardstick when the implementation changes.
#
# Model FLOPs follow the usual 2 * N * tokens for inference (the program's
# ``launch/analytic.py`` counts the same), with N the parameters a token
# passes through (every layer and the output head; the embedding lookup is a
# gather), plus attention's 4 * layers * heads * head_dim per token and
# position attended (scores and weighted sum).

def dense_params(s: Shapes) -> int:
    """Parameters each token's matrix products pass through."""
    return s.layers * s.layer_params() + s.head_params()


def token_flops(s: Shapes, ctx: int) -> float:
    """FLOPs of one token that attends over ``ctx`` positions (itself
    included)."""
    return 2.0 * dense_params(s) + 4.0 * s.layers * s.heads * s.head_dim * ctx


def prefill_flops(s: Shapes, start: int, end: int) -> float:
    """FLOPs to prefill positions [start, end) after ``start`` cached ones:
    position p attends over p + 1 positions."""
    n = end - start
    ctx_sum = (start + 1 + end) * n / 2.0
    return (2.0 * dense_params(s) * n
            + 4.0 * s.layers * s.heads * s.head_dim * ctx_sum)


def weight_bytes(s: Shapes) -> float:
    """Bytes of the weights one decode step reads: every layer and the
    output head (the embedding rows a step gathers are negligible)."""
    return float(dense_params(s) * jnp.dtype(s.dtype).itemsize)


def decode_work(s: Shapes, steps: int, ctx_sum: int, tokens: int):
    """(FLOPs, bytes) of ``steps`` decode steps that produced ``tokens``
    tokens over a summed live context of ``ctx_sum`` positions."""
    flops = (2.0 * dense_params(s) * tokens
             + 4.0 * s.layers * s.heads * s.head_dim * ctx_sum)
    nbytes = steps * weight_bytes(s) + ctx_sum * s.kv_bytes_per_token()
    return flops, nbytes
