"""Speculative decoding: drafter construction + the drafter's device plane.

The paper's advice #2 — offload latency-insensitive work to the secondary
endpoint — applied to decode latency: a small greedy **drafter** proposes
``draft_k`` tokens per slot, and the target model scores all k+1 positions
in ONE batched verify step instead of k+1 sequential decode dispatches.
The longest draft prefix matching the target's own greedy choices is
accepted; the rejected suffix is rolled back (stale cache entries for paged/
dense global attention, per-row state select for snapshot archs).  Greedy
acceptance uses the same ``jnp.argmax`` as the sampler's greedy path, so
accepted output is bit-identical to non-speculative greedy decode.

Three drafter sources, selected by ``ServeConfig.draft_model``:

  * ``"self:<n>"`` — **layer-skip** truncation of the target: the first n
    stacked layers plus the target's own embedding / final norm / unembed.
    Zero extra training, near-zero extra memory (parameters are shared
    slices), and high agreement when the deep layers refine rather than
    redirect the prediction.
  * ``"self-int8"`` — the target's own depth with every matrix weight
    rounded to the int8 grid (symmetric per-tensor fake quantization).
    High agreement, but the drafter costs as much compute as the target —
    useful for exercising rollback paths, not for speedup on its own.
  * any other value — an arch name from ``configs/`` (e.g. a
    ``smollm_360m``-class config next to a larger target), independently
    initialized.  Must share the target's vocabulary.

The drafter always runs greedy, dense (non-paged) decode over its own
per-slot cache, so it is restricted to global-attention decoder-only
configs (``supports_paging``) — its rejected cache entries roll back for
free under the causal mask.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.model import ModelConfig
from repro.config.run import ServeConfig
from repro.models.transformer import (
    ExecPolicy, init_decode_state, init_params, supports_paging)
from repro.serve import programs
from repro.serve.backends import place_beside


def make_draft_config(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """The layer-skip drafter's config: the target truncated to its first
    ``n_layers`` layers."""
    if len(cfg.pattern) != 1:
        raise ValueError(
            f"draft_model='self:<n>' needs a single-entry layer pattern to "
            f"slice the stacked params; {cfg.arch_id} has {cfg.pattern}")
    if not 1 <= n_layers <= cfg.num_layers:
        raise ValueError(
            f"draft_model='self:{n_layers}': need 1 <= n <= "
            f"{cfg.num_layers} (target depth)")
    return dataclasses.replace(cfg, num_layers=n_layers)


def slice_draft_params(params: Any, n_layers: int) -> Any:
    """Share the target's parameters with a layer-skip drafter: the stacked
    layer leaves are sliced to their first ``n_layers`` repetitions; embed,
    final norm and (tied or explicit) unembed are reused as-is.  No copy of
    anything large — slices alias the target's buffers until donated."""
    out = {k: v for k, v in params.items() if k not in ("layers", "tail")}
    out["layers"] = {
        i: jax.tree.map(lambda a: a[:n_layers], sub)
        for i, sub in params["layers"].items()}
    out["tail"] = {}
    return out


def quantize_draft_params(params: Any) -> Any:
    """Round every layer matrix to the int8 grid (symmetric per-tensor fake
    quantization, stored back in the model dtype).  Embeddings and 1-D norm
    scales stay exact — the drafter disagrees with the target only where
    the quantization noise flips an argmax."""
    def q(leaf):
        if getattr(leaf, "ndim", 0) < 2:
            return leaf
        xf = leaf.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf)) / 127.0, 1e-8)
        return (jnp.round(xf / scale) * scale).astype(leaf.dtype)

    out = {k: v for k, v in params.items() if k not in ("layers", "tail")}
    out["layers"] = jax.tree.map(q, params["layers"])
    out["tail"] = jax.tree.map(q, params["tail"])
    return out


def resolve_drafter(cfg: ModelConfig, params: Any,
                    scfg: ServeConfig) -> Tuple[ModelConfig, Any]:
    """Build (draft_cfg, draft_params) from ``ServeConfig.draft_model``."""
    spec = scfg.draft_model
    if spec.startswith("self:"):
        n = int(spec.split(":", 1)[1])
        dcfg = make_draft_config(cfg, n)
        dparams = slice_draft_params(params, n)
    elif spec == "self-int8":
        dcfg = cfg
        dparams = quantize_draft_params(params)
    else:
        from repro.config import get_config
        dcfg = get_config(spec)
        if dcfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"drafter {spec!r} vocab ({dcfg.vocab_size}) != target "
                f"vocab ({cfg.vocab_size}): verify compares token ids, the "
                "models must share a vocabulary")
        dparams = init_params(jax.random.PRNGKey(scfg.seed), dcfg)
    if not supports_paging(dcfg):
        raise ValueError(
            f"drafter {spec!r} ({dcfg.arch_id}) must be a global-attention "
            "decoder-only config: the draft plane relies on causal masking "
            "to roll rejected entries back for free")
    return dcfg, dparams


class DraftPlane:
    """The drafter's device half: its own dense per-slot decode states plus
    the fused admit/propose programs.  One instance per engine; all methods
    run on the engine loop thread.

    Each macro step ``propose`` reads the *target's* token/position mirrors
    (the drafter keeps no mirrors of its own — the target's committed
    sequence is the ground truth) and runs a k+1-iteration greedy scan:
    iteration i feeds the chunk's i-th token, writes its KV and emits the
    next proposal, so after the scan the drafter's cache covers every
    position the next chunk's context needs."""

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig,
                 policy: ExecPolicy = ExecPolicy()):
        if scfg.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {scfg.draft_k}")
        self.cfg, self.params = cfg, params
        self.k = scfg.draft_k
        self.capacity = scfg.max_seq_len
        self._admit_prog = programs.draft_admit_program(
            cfg, policy, scfg.max_seq_len)
        self._propose_prog = programs.draft_propose_program(
            cfg, policy, scfg.draft_k)
        self.states = place_beside(
            init_decode_state(cfg, scfg.max_batch, capacity=scfg.max_seq_len),
            params)

    def admit(self, slot: int, prompt: np.ndarray, bucket: int) -> None:
        """Prefill ``prompt`` into the drafter's state at ``slot`` (one
        fused dispatch, no sampling)."""
        L = len(prompt)
        S = max(min(bucket, self.capacity), L, 1)
        toks = np.zeros((1, S), np.int32)
        toks[0, :L] = prompt
        positions = np.arange(S, dtype=np.int32)[None, :]
        batch = {"tokens": jnp.asarray(toks),
                 "positions": jnp.asarray(positions),
                 "length": jnp.asarray(L, jnp.int32)}
        self.states = self._admit_prog(self.params, self.states, batch,
                                       jnp.asarray(slot, jnp.int32))

    def propose(self, tok: jax.Array, pos: jax.Array,
                caps: jax.Array) -> jax.Array:
        """k greedy proposals (B, k) continuing each row's committed
        sequence; drafter state advances through the whole chunk."""
        self.states, drafts = self._propose_prog(
            self.params, self.states, tok, pos, caps)
        return drafts


def build_draft_plane(cfg: ModelConfig, params: Any, scfg: ServeConfig,
                      policy: ExecPolicy = ExecPolicy(),
                      drafter: Optional[Tuple[ModelConfig, Any]] = None,
                      ) -> DraftPlane:
    """The engine-facing constructor: an explicit (config, params) drafter
    override wins (tests / benchmarks build custom drafters); otherwise the
    pair is resolved from ``ServeConfig.draft_model``."""
    if drafter is not None:
        dcfg, dparams = drafter
        if not supports_paging(dcfg):
            raise ValueError(
                f"explicit drafter ({dcfg.arch_id}) must be a "
                "global-attention decoder-only config")
    else:
        dcfg, dparams = resolve_drafter(cfg, params, scfg)
    return DraftPlane(dcfg, dparams, scfg, policy)
