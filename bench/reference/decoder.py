"""Plain decoder-only transformer in float32: the reference for ``correct``.

Shared by every architecture: rms norm, rotary embeddings, blocked causal
attention and ``logits_at``, which runs the layer function that an
architecture's file in ``bench/arch/`` passes (another block's equations go
in ``bench/reference/<name>.py``).  The Llama/Mistral block
(``dense_block``), written from the published description and nothing of
the program:

    h   = x + Wo . attn(RoPE(Wq . rms(x)), RoPE(Wk . rms(x)), Wv . rms(x))
    out = h + Wdown . (silu(Wgate . rms(h)) * Wup . rms(h))

with rms(x) = x / sqrt(mean(x^2) + eps) * g, grouped-query causal attention
(query head i reads key/value head i // (heads / kv_heads)), rotary
embeddings on the two halves of each head (rotate-half), a final rms norm and
the output head (the embedding's transpose when tied).

It runs layer by layer over a batch of whole sequences, making each layer's
weights from the seed just before it is needed, so that it fits on the chip
after the served model is freed.  Every matrix product runs at
``Precision.HIGHEST``.  ``fp8=True`` is the control: every matrix product's
operands are rounded to float8 e4m3 with one scale per tensor, the precision
a later change might be tempted to serve in.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import seed_key, served

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
PAD = 512              # sequences are padded to a multiple of this


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    # x (N, T, H, n); pos (T,)
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]     # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, fp8: bool):
    # q (N, T, H, n), k/v (N, T, J, n); causal over T, in query blocks.
    N, T, H, n = q.shape
    J = k.shape[2]
    G = H // J
    q = q.reshape(N, T, J, G, n) * (n ** -0.5)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = mm("nsjgd,ntjd->njgst", qb, k, fp8)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("njgst,ntjd->nsjgd", p, v, fp8)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))     # (nb, N, Q, J, G, n)
    return jnp.moveaxis(out, 0, 1).reshape(N, T, H * n)


def dense_block(x, w, s, fp8: bool):
    """One Llama/Mistral block on activations ``x`` (N, T, d) with its
    float32 weights ``w`` (``layer_weights`` of ``bench/arch/dense.py``)."""
    N, T, _ = x.shape
    n = s.head_dim
    pos = jnp.arange(T)
    h = rms(x, w["attn_norm"], s.eps)
    q = mm("ntd,de->nte", h, w["wq"], fp8).reshape(N, T, s.heads, n)
    k = mm("ntd,de->nte", h, w["wk"], fp8).reshape(N, T, s.kv_heads, n)
    v = mm("ntd,de->nte", h, w["wv"], fp8).reshape(N, T, s.kv_heads, n)
    q, k = rope(q, pos, s.rope_theta), rope(k, pos, s.rope_theta)
    x = x + mm("nte,ed->ntd", attention(q, k, v, fp8), w["wo"], fp8)
    h = rms(x, w["mlp_norm"], s.eps)
    g = mm("ntd,df->ntf", h, w["w_gate"], fp8)
    u = mm("ntd,df->ntf", h, w["w_up"], fp8)
    return x + mm("ntf,fd->ntd", jax.nn.silu(g) * u, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("global_weights", "s"))
def _embed(tokens, key, global_weights, s):
    return served(global_weights(key, s), s)["embed"][tokens]


@functools.partial(jax.jit, static_argnames=("global_weights", "s", "fp8"))
def _logits(x, rows, key, global_weights, s, fp8: bool):
    g = served(global_weights(key, s), s)
    head = g["embed"].T if s.tied else g["unembed"]
    h = rms(x, g["final_norm"], s.eps)
    picked = h[rows[:, 0], rows[:, 1]]                      # (R, d)
    return mm("rd,dv->rv", picked, head, fp8)


def logits_at(seed: int, s, seqs: Sequence[np.ndarray],
              wanted: Sequence[Sequence[int]], layer: Callable,
              global_weights: Callable, fp8: bool = False) -> np.ndarray:
    """Logits (R, vocab) at ``wanted[i]`` positions of each sequence ``i``,
    rows in order.  Position ``p``'s logits predict token ``p + 1``.

    The architecture gives ``layer(x, key, i, s, fp8)``, its layer ``i``
    (a traced int32) on activations ``x`` (N, T, d) with weights made from
    ``key``, and ``global_weights(key, s)``: float32 ``embed`` (vocab, d),
    ``final_norm`` and, unless ``s.tied``, ``unembed`` (d, vocab).  The
    shapes ``s`` also give ``layers``, ``eps`` and the served ``dtype``."""
    key = seed_key(seed)
    T = max(len(t) for t in seqs)
    T = -(-T // PAD) * PAD
    toks = np.zeros((len(seqs), T), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    x = _embed(jnp.asarray(toks), key, global_weights, s)
    for i in range(s.layers):
        x = layer(x, key, jnp.asarray(i, jnp.int32), s, fp8)
    rows = np.array([(i, p) for i, ps in enumerate(wanted) for p in ps],
                    np.int32).reshape(-1, 2)
    return np.asarray(_logits(x, jnp.asarray(rows), key, global_weights, s,
                              fp8))


def served_gaps(seed: int, s, requests: Sequence[Tuple[np.ndarray, List[int]]],
                layer: Callable, global_weights: Callable,
                control: bool = False) -> np.ndarray:
    """For each served token of each ``(prompt, served tokens)`` request: how
    far the reference's logit of that token lies below the reference's best.
    With ``control``, the tokens are instead those the fp8 computation puts
    first at the same positions.  ``layer`` and ``global_weights`` are the
    architecture's, as ``logits_at`` takes them."""
    seqs, wanted, served_toks = [], [], []
    for prompt, out in requests:
        L = len(prompt)
        seqs.append(np.concatenate([np.asarray(prompt, np.int32),
                                    np.asarray(out[:-1], np.int32)]))
        wanted.append(list(range(L - 1, L - 1 + len(out))))
        served_toks.extend(int(t) for t in out)
    ref = logits_at(seed, s, seqs, wanted, layer, global_weights)
    if control:
        picked = logits_at(seed, s, seqs, wanted, layer, global_weights,
                           fp8=True).argmax(-1)
    else:
        picked = np.asarray(served_toks)
    return ref.max(-1) - ref[np.arange(len(picked)), picked]
