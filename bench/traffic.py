"""One generator for every traffic mix: a mix is a data file of parameters.

``bench/traffic/<mix>.json`` says how requests arrive and how long they are:

    arrival      {"kind": "sessions", "rate_per_s": r}: an open loop, sessions
                 arriving as a Poisson process at ``r`` a second;
                 {"kind": "backlog", "count": n}: ``n`` requests queued before
                 the window opens.
    system       optional; {"count", "min", "max", "zipf"}: a pool of shared
                 system prompts with lengths uniform in [min, max], picked by
                 Zipf popularity.
    turns        {"min", "max"}: turns per session, uniform.
    think_s_mean mean of the exponential gap between a session's turns.
    message      {"median", "min", "max", "sigma"}: the new text of each turn
                 (lognormal, clipped).
    budget       likewise for the tokens each turn asks for.
    max_context  a session ends where its next turn's prompt plus budget
                 would pass this.
    block        sessions per block (below).

A turn's prompt is the system prompt, then every earlier turn's message and
answer, then its own message.  The earlier answers are drawn with the length
that turn asked for, so every input is fixed by the seed and none depends on
what the system served.

Every seed gets the same work in another order: the sizes (lengths, budgets,
turns, think times, gaps between arrivals, system prompt picks) come from a
fixed stream, and the seed shuffles the sessions and the gaps within each
block of ``block`` sessions and draws the token ids, uniform over the
vocabulary.  So two seeds differ in order and content, not in load.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

SIZES_SEED = 0x5EED


@dataclasses.dataclass(frozen=True)
class Item:
    due_s: float                 # seconds after the traffic's start
    session: int
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int


def _lognormal(rng, p: Dict[str, Any]) -> int:
    v = rng.lognormal(np.log(p["median"]), p["sigma"])
    return int(np.clip(round(v), p["min"], p["max"]))


def is_backlog(mix: Dict[str, Any]) -> bool:
    return mix["arrival"]["kind"] == "backlog"


def _session_sizes(sizes, mix, sys_lens, popularity):
    """(system prompt index or -1, [(message, budget, think_s), ...])."""
    sys_idx = (int(sizes.choice(len(sys_lens), p=popularity))
               if sys_lens else -1)
    ctx = sys_lens[sys_idx] if sys_lens else 0
    turns = []
    for _ in range(int(sizes.integers(mix["turns"]["min"],
                                      mix["turns"]["max"] + 1))):
        msg = _lognormal(sizes, mix["message"])
        budget = _lognormal(sizes, mix["budget"])
        think = float(sizes.exponential(mix.get("think_s_mean", 0.0)))
        if ctx + msg + budget > mix["max_context"]:
            break
        turns.append((msg, budget, think))
        ctx += msg + budget
    return sys_idx, turns


def _system(mix, sizes, rng, vocab):
    """The shared system prompts and their popularity (none without a
    ``system`` entry)."""
    system = mix.get("system")
    if not system:
        return [], None
    lens = [int(sizes.integers(system["min"], system["max"] + 1))
            for _ in range(system["count"])]
    w = 1.0 / np.arange(1, system["count"] + 1) ** system["zipf"]
    return ([rng.integers(0, vocab, size=k, dtype=np.int32) for k in lens],
            w / w.sum())


def system_prompts(mix: Dict[str, Any], seed: int,
                   vocab: int) -> List[np.ndarray]:
    """The shared system prompts ``generate`` uses for ``seed``."""
    return _system(mix, np.random.default_rng(SIZES_SEED),
                   np.random.default_rng([seed, SIZES_SEED]), vocab)[0]


def generate(mix: Dict[str, Any], seed: int, horizon_s: float,
             vocab: int) -> List[Item]:
    """The requests of ``mix`` for ``seed``, sorted by due time.  Sessions
    start in [0, horizon_s); a backlog is all due at 0."""
    sizes = np.random.default_rng(SIZES_SEED)
    rng = np.random.default_rng([seed, SIZES_SEED])
    arrival = mix["arrival"]
    sys_prompts, popularity = _system(mix, sizes, rng, vocab)
    sys_lens = [len(p) for p in sys_prompts]
    if is_backlog(mix):
        n = arrival["count"]
    else:
        n = int(np.ceil(arrival["rate_per_s"] * horizon_s * 1.5)) + 1
    specs = [_session_sizes(sizes, mix, sys_lens, popularity)
             for _ in range(n)]
    gaps = (np.zeros(n) if is_backlog(mix)
            else sizes.exponential(1.0 / arrival["rate_per_s"], n))
    block = int(mix.get("block", 16))
    order = np.concatenate([b + rng.permutation(min(block, n - b))
                            for b in range(0, n, block)])
    gap_order = np.concatenate([b + rng.permutation(min(block, n - b))
                                for b in range(0, n, block)])

    items: List[Item] = []
    t = 0.0
    for sid in range(n):
        t += gaps[gap_order[sid]]
        if not is_backlog(mix) and t >= horizon_s:
            break
        sys_idx, turns = specs[order[sid]]
        ctx = sys_prompts[sys_idx] if sys_idx >= 0 else np.zeros(0, np.int32)
        due = t
        for msg, budget, think in turns:
            prompt = np.concatenate(
                [ctx, rng.integers(0, vocab, size=msg, dtype=np.int32)])
            items.append(Item(due, sid, prompt, budget))
            ctx = np.concatenate(
                [prompt, rng.integers(0, vocab, size=budget, dtype=np.int32)])
            due += think
    items.sort(key=lambda it: (it.due_s, it.session))
    return items
