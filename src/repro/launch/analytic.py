"""Analytic FLOP/byte accounting.

Two jobs:

1. ``model_flops`` — the roofline's MODEL_FLOPS: 6·N_active·tokens for
   training, 2·N_active·tokens for inference (the "useful" compute).

2. ``scan_corrections`` — XLA's ``cost_analysis`` counts a while-loop body
   exactly ONCE (verified empirically), so programs containing scans
   under-report flops/bytes.  The dry-run unrolls layers
   (``scan_layers=False``) and decode is scan-free, but three scans remain by
   design (they bound memory): the chunked-attention q/kv loops, the RWKV6
   chunk loop, and the chunked cross-entropy loop.  Each has a statically
   known trip count and per-body cost, so the correction
   ``(trips - 1) x body_cost`` restores exact totals.  A test validates
   corrected HLO flops against a fully-unrolled compile on small shapes.

Only matmul flops are counted (2mnk), the standard convention; elementwise
softmax/norm work is < 2% at these widths and is ignored symmetrically in
both the analytic and the corrected-HLO numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.config.model import (
    MIX_ATTN, MIX_ATTN_CROSS, MIX_ATTN_LOCAL, MIX_RGLRU, MIX_RWKV6,
    ModelConfig)
from repro.config.shapes import ShapeSpec

RWKV_CHUNK = 64
XENT_CHUNK = 512


def _dtype_bytes(cfg: ModelConfig) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


# ----------------------------------------------------------------------------
# Forward matmul flops
# ----------------------------------------------------------------------------

def _mlp_flops_per_token(cfg: ModelConfig) -> float:
    d, f = cfg.d_model, cfg.d_ff
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    if cfg.num_experts:
        # capacity routing: every slot computes, incl. padding
        eff = cfg.experts_per_token * cfg.capacity_factor
        per = (6 if gated else 4) * d * f
        return eff * per + 2 * d * cfg.num_experts      # + router
    if cfg.mlp_kind == "rwkv_cmix":
        return 2 * d * f + 2 * f * d + 2 * d * d
    return (6 if gated else 4) * d * f


def _attn_proj_flops_per_token(cfg: ModelConfig) -> float:
    d = cfg.d_model
    return 2 * d * cfg.q_dim + 4 * d * cfg.kv_dim + 2 * cfg.q_dim * d


def _attention_compute_flops(cfg: ModelConfig, b: int, s: int, t: int) -> float:
    """qk^T + pv over ALL pairs (the jnp path masks, it does not prune)."""
    return 4.0 * b * cfg.num_heads * s * t * cfg.head_dim


def _mixer_flops(cfg: ModelConfig, kind: str, b: int, s: int, t: int,
                 mem: int) -> float:
    """Per-layer mixer flops for a (b, s) input attending over t keys."""
    d = cfg.d_model
    if kind in (MIX_ATTN, MIX_ATTN_LOCAL, MIX_ATTN_CROSS):
        t_eff = min(t, cfg.sliding_window) if kind == MIX_ATTN_LOCAL and s == 1 \
            else t
        fl = b * s * _attn_proj_flops_per_token(cfg)
        fl += _attention_compute_flops(cfg, b, s, t_eff)
        if kind == MIX_ATTN_CROSS:
            fl += b * s * (2 * d * cfg.q_dim + 2 * cfg.q_dim * d)
            fl += b * mem * 4 * d * cfg.kv_dim          # memory kv (per call)
            fl += _attention_compute_flops(cfg, b, s, mem)
        return fl
    if kind == MIX_RGLRU:
        w = cfg.rglru_width
        fl = b * s * (4 * d * w + 2 * w * d)            # wx, wy, wo
        fl += b * s * 4 * w * w                          # gates
        fl += b * s * 2 * cfg.rglru_conv_width * w       # conv
        return fl
    if kind == MIX_RWKV6:
        n = cfg.rwkv_head_size
        fl = b * s * 5 * 2 * d * d                       # r,k,v,g,o
        fl += b * s * (2 * d * 64 + 2 * 64 * d)          # decay lora
        fl += b * s * 4 * d * (n + RWKV_CHUNK)           # chunked recurrence
        return fl
    raise ValueError(kind)


def forward_flops(cfg: ModelConfig, b: int, s: int, t: int) -> float:
    """Total forward matmul flops for (b, s) tokens with t-key context."""
    mem = cfg.frontend_seq_len or 0
    fl = 0.0
    for kind in cfg.layer_kinds:
        fl += _mixer_flops(cfg, kind, b, s, t, mem)
        fl += b * s * _mlp_flops_per_token(cfg)
    if cfg.is_encoder_decoder and mem:
        for _ in range(cfg.num_encoder_layers):
            fl += _mixer_flops(cfg, MIX_ATTN, b, mem, mem, 0)
            fl += b * mem * _mlp_flops_per_token(cfg)
    fl += b * s * 2 * cfg.d_model * cfg.vocab_size       # logits
    return fl


def step_flops(cfg: ModelConfig, spec: ShapeSpec) -> float:
    """Analytic flops of the lowered step (train: fwd + 2x bwd)."""
    b = spec.global_batch
    if spec.kind == "train":
        return 3.0 * forward_flops(cfg, b, spec.seq_len, spec.seq_len)
    if spec.kind == "prefill":
        return forward_flops(cfg, b, spec.seq_len, spec.seq_len)
    # decode: 1 token against a seq_len cache (encoder already folded)
    fl = 0.0
    mem = cfg.frontend_seq_len or 0
    for kind in cfg.layer_kinds:
        t = spec.seq_len
        if kind == MIX_ATTN_LOCAL and cfg.sliding_window:
            t = min(t, cfg.sliding_window)
        if kind == MIX_RWKV6:
            n = cfg.rwkv_head_size
            fl += b * (5 * 2 * cfg.d_model ** 2 + 4 * cfg.d_model * n
                       + 2 * cfg.d_model * 64 * 2)
        elif kind == MIX_RGLRU:
            w = cfg.rglru_width
            fl += b * (6 * cfg.d_model * w + 4 * w * w)
        else:
            fl += b * _attn_proj_flops_per_token(cfg)
            fl += _attention_compute_flops(cfg, b, 1, t)
            if kind == MIX_ATTN_CROSS:
                fl += b * (2 * cfg.d_model * cfg.q_dim + 2 * cfg.q_dim * cfg.d_model)
                fl += _attention_compute_flops(cfg, b, 1, mem or 256)
        fl += b * _mlp_flops_per_token(cfg)
    fl += b * 2 * cfg.d_model * cfg.vocab_size
    return fl


def model_flops(cfg: ModelConfig, spec: ShapeSpec) -> float:
    """6·N_active·D (train) / 2·N_active·D (inference)."""
    n = cfg.active_param_count()
    if spec.kind == "train":
        return 6.0 * n * spec.global_batch * spec.seq_len
    if spec.kind == "prefill":
        return 2.0 * n * spec.global_batch * spec.seq_len
    return 2.0 * n * spec.global_batch  # one token


# ----------------------------------------------------------------------------
# Scan-trip-count corrections for the HLO numbers
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class ScanCorrection:
    flops: float
    bytes: float
    detail: dict


def _batch_degree(b: int, mesh_shape: dict) -> int:
    """How many ways XLA shards the batch dim (data, then pod)."""
    deg = 1
    for ax in ("data", "pod"):
        n = mesh_shape.get(ax, 1)
        if n > 1 and b % (deg * n) == 0:
            deg *= n
    return deg


def sharding_degrees(cfg: ModelConfig, spec: ShapeSpec,
                     mesh_shape: dict) -> dict:
    """Per-op-family partition degree under the logical rules.

    cost_analysis reports the PER-DEVICE SPMD program, so corrections (which
    are computed from global logical shapes) must be divided by how many ways
    the corrected computation is actually partitioned.  Replication (e.g.
    smollm's 15 heads on a 16-way model axis) gives degree 1 on that axis —
    the resulting inflated per-device flops is real, visible redundancy.
    """
    dp = _batch_degree(spec.global_batch, mesh_shape)
    mp = mesh_shape.get("model", 1)
    return {
        "attention": dp * (mp if cfg.num_heads and
                           cfg.num_heads % mp == 0 else 1),
        "rwkv": dp * (mp if cfg.d_model % mp == 0 else 1),
        "xent": dp * (mp if cfg.vocab_size % mp == 0 else 1),
        "mlp": dp * (mp if cfg.d_ff % mp == 0 else 1),
        "moe": (dp if cfg.moe_dispatch == "batched" else 1) *
               (mp if cfg.num_experts and cfg.num_experts % mp == 0 else 1),
        "dp": dp, "mp": mp,
    }


def scan_corrections(cfg: ModelConfig, spec: ShapeSpec,
                     q_chunk: int, kv_chunk: int,
                     mesh_shape: Optional[dict] = None,
                     layer_scan_reps: int = 0) -> ScanCorrection:
    """PER-DEVICE extra (flops, bytes) that cost_analysis misses.

    Each known scan contributes ``(executions - 1) x per-device body cost``.
    With ``layer_scan_reps`` (scan_layers=True), the whole pattern body is a
    while loop: its non-chunked parts get (reps-1) x body and the chunked
    parts get (reps x trips - 1) x body.
    """
    b, s = spec.global_batch, spec.seq_len
    dt = _dtype_bytes(cfg)
    deg = sharding_degrees(cfg, spec, mesh_shape or {})
    extra_f, extra_b = 0.0, 0.0
    detail = {"degrees": deg}
    reps = max(layer_scan_reps, 1)
    pat = cfg.pattern if layer_scan_reps else cfg.layer_kinds

    if spec.kind in ("train", "prefill") and q_chunk and kv_chunk \
            and s > q_chunk:
        nq, nk = s // q_chunk, s // kv_chunk
        pairs = nq * nk
        mult = 3.0 if spec.kind == "train" else 1.0
        n_attn = sum(1 for k in pat
                     if k in (MIX_ATTN, MIX_ATTN_LOCAL, MIX_ATTN_CROSS))
        pair_f = 4.0 * b * cfg.num_heads * q_chunk * kv_chunk * cfg.head_dim
        pair_b = b * cfg.num_heads * (q_chunk + 2 * kv_chunk) * cfg.head_dim * dt \
            + b * cfg.num_heads * q_chunk * cfg.head_dim * 4 * 2  # acc rw
        execs = reps * pairs
        extra_f += n_attn * (execs - 1) * pair_f * mult / deg["attention"]
        extra_b += n_attn * (execs - 1) * pair_b * mult / deg["attention"]
        detail["attention_pairs"] = pairs

    if spec.kind in ("train", "prefill"):
        n_rwkv = sum(1 for k in pat if k == MIX_RWKV6)
        if n_rwkv and s > RWKV_CHUNK:
            nc = s // RWKV_CHUNK
            n = cfg.rwkv_head_size
            mult = 3.0 if spec.kind == "train" else 1.0
            chunk_f = 4.0 * b * RWKV_CHUNK * cfg.d_model * (n + RWKV_CHUNK)
            chunk_b = 4 * b * RWKV_CHUNK * cfg.d_model * 4 \
                + b * (cfg.d_model // n) * n * n * 4 * 2
            execs = reps * nc
            extra_f += n_rwkv * (execs - 1) * chunk_f * mult / deg["rwkv"]
            extra_b += n_rwkv * (execs - 1) * chunk_b * mult / deg["rwkv"]
            detail["rwkv_chunks"] = nc

    if layer_scan_reps and spec.kind in ("train", "prefill") and reps > 1:
        # non-chunked per-pattern-body work: projections + mlp (+ recurrent
        # projections), each at its own partition degree
        mult = 3.0 if spec.kind == "train" else 1.0
        body_f = 0.0
        for kind in cfg.pattern:
            if kind in (MIX_ATTN, MIX_ATTN_LOCAL, MIX_ATTN_CROSS):
                f = b * s * _attn_proj_flops_per_token(cfg)
                if kind == MIX_ATTN_CROSS:
                    m = cfg.frontend_seq_len or 256
                    f += b * s * 4 * cfg.d_model * cfg.q_dim / 2
                    f += _attention_compute_flops(cfg, b, s, m)
                body_f += f / deg["attention"]
            elif kind == MIX_RGLRU:
                w = cfg.rglru_width
                body_f += b * s * (6 * cfg.d_model * w + 4 * w * w) \
                    / deg["rwkv"]
            elif kind == MIX_RWKV6:
                body_f += b * s * (10 * cfg.d_model ** 2
                                   + 4 * cfg.d_model * 64) / deg["rwkv"]
            if cfg.num_experts:
                body_f += b * s * _mlp_flops_per_token(cfg) / deg["moe"]
            else:
                body_f += b * s * _mlp_flops_per_token(cfg) / deg["mlp"]
        extra_f += (reps - 1) * body_f * mult
        extra_b += (reps - 1) * _dtype_bytes(cfg) * b * s * cfg.d_model * 8 \
            / deg["dp"] * mult
        detail["layer_scan_reps"] = reps

    if spec.kind == "train" and s > XENT_CHUNK:
        nc = s // XENT_CHUNK
        chunk_f = 3.0 * 2.0 * b * XENT_CHUNK * cfg.d_model * cfg.vocab_size
        chunk_b = b * XENT_CHUNK * (cfg.d_model * dt + cfg.vocab_size * 4) \
            + cfg.d_model * cfg.vocab_size * dt
        extra_f += (nc - 1) * chunk_f / deg["xent"]
        extra_b += (nc - 1) * chunk_b / deg["xent"]
        detail["xent_chunks"] = nc

    return ScanCorrection(extra_f, extra_b, detail)
