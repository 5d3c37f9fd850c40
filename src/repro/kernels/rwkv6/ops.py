"""jit'd wrapper for the RWKV6 chunked-recurrence kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.rwkv6.kernel import rwkv6_pallas
from repro.kernels.rwkv6.ref import rwkv6_ref


def supported(r, k=None, v=None, w=None, u=None, *, chunk: int = 64) -> bool:
    B, T, H, N = r.shape
    return T % min(chunk, T) == 0 and N % 8 == 0


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6(r, k, v, w, u, *, chunk: int = 64) -> jax.Array:
    """Model layout (B,T,H,N) + u (H,N) -> y (B,T,H,N)."""
    B, T, H, N = r.shape
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, N)
    ub = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    y = rwkv6_pallas(to_bh(r), to_bh(k), to_bh(v), to_bh(w), ub,
                     chunk=chunk, interpret=interpret_mode())
    return y.reshape(B, H, T, N).transpose(0, 2, 1, 3)
