"""Residual blocks for every mixer kind, with train and decode paths.

Block state (decode) by kind:
  attn / attn_local : KV cache dict (ring-buffered for local)
  attn_cross        : KV cache + per-layer projected memory KV (from prefill)
  rglru             : {"h", "conv"}
  rwkv6             : {"S", "x_prev", "cmix_prev"}
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.model import (
    MIX_ATTN, MIX_ATTN_CROSS, MIX_ATTN_LOCAL, MIX_RGLRU, MIX_RWKV6, ModelConfig)
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import init_rmsnorm, rms_norm, split_keys


def init_block(key, kind: str, cfg: ModelConfig, dtype) -> dict:
    k1, k2, k3, k4 = split_keys(key, 4)
    p: dict = {"norm1": init_rmsnorm(cfg.d_model, dtype),
               "norm2": init_rmsnorm(cfg.d_model, dtype)}
    if kind in (MIX_ATTN, MIX_ATTN_LOCAL, MIX_ATTN_CROSS):
        p["mixer"] = attn_mod.init_attention(k1, cfg, dtype)
        if kind == MIX_ATTN_CROSS:
            p["norm_c"] = init_rmsnorm(cfg.d_model, dtype)
            p["cross"] = attn_mod.init_cross_attention(k2, cfg, dtype)
    elif kind == MIX_RGLRU:
        p["mixer"] = rglru_mod.init_rglru(k1, cfg, dtype)
    elif kind == MIX_RWKV6:
        p["mixer"] = rwkv_mod.init_rwkv6(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    p["mlp"] = mlp_mod.init_mlp(k3, cfg, dtype)
    return p


def init_block_state(kind: str, cfg: ModelConfig, batch: int, capacity: int,
                     dtype) -> dict:
    """Decode-time state for one block. ``capacity`` = KV cache slots
    (window size for local attention — constant-memory long context)."""
    if kind in (MIX_ATTN, MIX_ATTN_LOCAL, MIX_ATTN_CROSS):
        cap = capacity
        if kind == MIX_ATTN_LOCAL and cfg.sliding_window:
            cap = min(capacity, cfg.sliding_window)
        st = {"cache": attn_mod.init_cache(cfg, batch, cap, dtype)}
        if kind == MIX_ATTN_CROSS:
            m = cfg.frontend_seq_len or 256
            j, n = cfg.num_kv_heads, cfg.head_dim
            st["mem_k"] = jnp.zeros((batch, m, j, n), dtype)
            st["mem_v"] = jnp.zeros((batch, m, j, n), dtype)
        return st
    if kind == MIX_RGLRU:
        return rglru_mod.init_rglru_state(cfg, batch)
    if kind == MIX_RWKV6:
        st = rwkv_mod.init_rwkv6_state(cfg, batch)
        st["cmix_prev"] = jnp.zeros((batch, cfg.d_model), jnp.float32)
        return st
    raise ValueError(kind)


def apply_block(
    params: dict,
    kind: str,
    x: jax.Array,                    # (B, S, D)
    positions: jax.Array,            # (B, S)
    cfg: ModelConfig,
    *,
    memory: Optional[jax.Array] = None,   # (B, M, D) cross-attn memory
    state: Optional[dict] = None,
    causal: bool = True,
    page_table: Optional[jax.Array] = None,   # (B, M) paged-KV block table
    layer=None,                      # index into stacked page pools
    q_chunk: int = 0,
    kv_chunk: int = 0,
    use_kernel: bool = False,
    constrain_recurrence: bool = False,
) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (x_out, new_state, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_state: Optional[dict] = None
    h = rms_norm(x, params["norm1"], cfg.norm_eps)

    if kind in (MIX_ATTN, MIX_ATTN_LOCAL, MIX_ATTN_CROSS):
        window = cfg.sliding_window if kind == MIX_ATTN_LOCAL else 0
        cache = None if state is None else state["cache"]
        out, new_cache = attn_mod.self_attention(
            params["mixer"], h, positions, cfg, window=window, causal=causal,
            cache=cache, page_table=page_table, layer=layer, q_chunk=q_chunk,
            kv_chunk=kv_chunk, use_kernel=use_kernel)
        x = x + out
        if kind == MIX_ATTN_CROSS:
            hc = rms_norm(x, params["norm_c"], cfg.norm_eps)
            if state is not None and "mem_k" in state:
                mem_kv = (state["mem_k"], state["mem_v"])
                out_c, mem_kv = attn_mod.cross_attention(
                    params["cross"], hc, memory, cfg, memory_kv=None
                    if memory is not None else mem_kv)
            else:
                out_c, mem_kv = attn_mod.cross_attention(
                    params["cross"], hc, memory, cfg)
            x = x + out_c
        if state is not None:
            new_state = {"cache": new_cache}
            if kind == MIX_ATTN_CROSS:
                new_state["mem_k"], new_state["mem_v"] = mem_kv
    elif kind == MIX_RGLRU:
        out, new_state = rglru_mod.apply_rglru(
            params["mixer"], h, cfg, state=state, use_kernel=use_kernel)
        x = x + out
    elif kind == MIX_RWKV6:
        rw_state = None
        if state is not None:
            rw_state = {"S": state["S"], "x_prev": state["x_prev"]}
        out, rw_new = rwkv_mod.apply_rwkv6(
            params["mixer"], h, cfg, state=rw_state, use_kernel=use_kernel,
            constrain_recurrence=constrain_recurrence)
        x = x + out
        if rw_new is not None:
            new_state = dict(rw_new)
    else:
        raise ValueError(kind)

    h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
    shifted = None
    if cfg.mlp_kind == "rwkv_cmix" and state is not None:
        prev = state["cmix_prev"][:, None].astype(h2.dtype)
        shifted = jnp.concatenate([prev, h2[:, :-1]], axis=1)
    mlp_out, mlp_aux = mlp_mod.apply_mlp(params["mlp"], h2, cfg, shifted=shifted)
    if cfg.mlp_kind == "rwkv_cmix" and new_state is not None:
        new_state["cmix_prev"] = h2[:, -1].astype(jnp.float32)
    return x + mlp_out, new_state, aux + mlp_aux
