"""Model shapes and seeded weights, independent of the program.

A configuration file (``bench/configs/<name>.json``) holds the published
``config.json`` keys.  ``Shapes`` reads the sizes from it, and
``layer_weights``/``global_weights`` make every weight from the seed alone,
leaf by leaf and layer by layer, so that the reference can make the same
values again one layer at a time without anything the program made.

Weights are drawn in float32 and rounded to the served dtype; the reference
computes in float32 on those rounded values.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

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

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Shapes":
        for k, ok in _UNSUPPORTED.items():
            if c.get(k, ok) != ok:
                raise ValueError(f"{k}={c[k]!r} is not covered by the "
                                 "reference's equations")
        heads = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
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


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed up to 64 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, leaf: str, layer, shape, std: float) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[leaf]), layer)
    return std * jax.random.normal(k, shape, jnp.float32)


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


def served(tree, s: Shapes):
    """Round float32 weights to the served dtype and back: the values the
    program holds, in float32."""
    dt = jnp.dtype(s.dtype)
    return jax.tree.map(lambda a: a.astype(dt).astype(jnp.float32), tree)
