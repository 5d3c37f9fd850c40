"""Operations and bytes that serving needs, from the model's shapes.

These count the work the algorithm needs, not what one implementation
happens to move: a decode step reads every weight once and the K/V of the
live context of each active row, however attention is implemented.  So a
share of a roofline computed from them cannot pass 100%, and stays the
same yardstick when the implementation changes.

``model_flops`` follows the usual 2 * N * tokens for inference (the
program's ``launch/analytic.py`` counts the same), with N the parameters a
token passes through (every layer and the output head; the embedding lookup
is a gather), plus attention's 4 * layers * heads * head_dim per token and
position attended (scores and weighted sum).
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.weights import Shapes


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
