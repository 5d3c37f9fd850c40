"""jit'd wrapper: model-layout adapter + accelerator-registry entry (G1)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_bhsn
from repro.kernels.flash_attention.ref import attention_ref


def supported(q, k, v, *, q_pos=None, k_pos=None, causal=True, window=0,
              cap=0.0, bq: int = 128, bk: int = 128) -> bool:
    """Shape/dtype predicate (the DOCA-style narrow-interface contract)."""
    if cap and cap > 0.0:
        return False
    B, S, J, G, N = q.shape
    T = k.shape[1]
    if S % bq or T % bk:
        return False
    if N % 8:
        return False
    if k.shape != (B, T, J, N) or v.shape != (B, T, J, N):
        return False
    return True


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "bq", "bk"))
def flash_attention(q, k, v, *, q_pos=None, k_pos=None, causal=True,
                    window=0, cap=0.0, bq: int = 128, bk: int = 128):
    """Model layout: q (B,S,J,G,N) pre-scaled, k/v (B,T,J,N) -> (B,S,J,G,N)."""
    del q_pos, k_pos, cap   # kernel path covers standard train/prefill masks
    B, S, J, G, N = q.shape
    T = k.shape[1]
    bq = min(bq, S)
    bk = min(bk, T)
    qh = q.reshape(B, S, J * G, N).transpose(0, 2, 1, 3).reshape(B * J * G, S, N)
    kh = k.transpose(0, 2, 1, 3).reshape(B * J, T, N)
    vh = v.transpose(0, 2, 1, 3).reshape(B * J, T, N)
    out = flash_attention_bhsn(
        qh, kh, vh, group=G, causal=causal, window=window, scale=1.0,
        bq=bq, bk=bk, interpret=interpret_mode())
    return out.reshape(B, J * G, S, N).transpose(0, 2, 1, 3) \
              .reshape(B, S, J, G, N)


def flash_attention_ref(q, k, v, *, q_pos=None, k_pos=None, causal=True,
                        window=0, cap=0.0, **_):
    del q_pos, k_pos, cap
    B, S, J, G, N = q.shape
    T = k.shape[1]
    qh = q.reshape(B, S, J * G, N).transpose(0, 2, 1, 3).reshape(B * J * G, S, N)
    kh = k.transpose(0, 2, 1, 3).reshape(B * J, T, N)
    vh = v.transpose(0, 2, 1, 3).reshape(B * J, T, N)
    out = attention_ref(qh, kh, vh, group=G, causal=causal, window=window)
    return out.reshape(B, J * G, S, N).transpose(0, 2, 1, 3) \
              .reshape(B, S, J, G, N)
