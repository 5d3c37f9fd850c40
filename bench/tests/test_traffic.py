"""The traffic generator: fixed by the seed, the same work for every seed,
and sessions that really share their prefixes."""
import json
from collections import defaultdict

import numpy as np
import pytest

from bench import traffic
from bench.spec import BENCH

PAGE = 16


def mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name,horizon", [("chat-prefix", 30.0),
                                          ("chat-prefix-sat", 30.0),
                                          ("docs-backlog", 0.0)])
def test_same_seed_same_inputs(name, horizon):
    a = traffic.generate(mix(name), 7, horizon, 1000)
    b = traffic.generate(mix(name), 7, horizon, 1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.due_s, x.session, x.max_new_tokens) == \
            (y.due_s, y.session, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name,horizon", [("chat-prefix", 30.0),
                                          ("chat-prefix-sat", 30.0),
                                          ("docs-backlog", 0.0)])
def test_seeds_differ_in_order_not_in_load(name, horizon):
    big = 2**31 + 12345
    a = traffic.generate(mix(name), 7, horizon, 1000)
    b = traffic.generate(mix(name), big, horizon, 1000)
    assert any(not np.array_equal(x.prompt[:32], y.prompt[:32])
               for x, y in zip(a, b))
    if name == "docs-backlog":
        # The whole backlog: the same sizes, in another order.
        assert sorted((len(i.prompt), i.max_new_tokens) for i in a) == \
            sorted((len(i.prompt), i.max_new_tokens) for i in b)
        assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    else:
        # Load within a few sessions' worth: shuffles stay inside blocks.
        ta = sum(len(i.prompt) + i.max_new_tokens for i in a)
        tb = sum(len(i.prompt) + i.max_new_tokens for i in b)
        assert abs(ta - tb) / ta < 0.1


def test_bounds_of_each_request():
    m = mix("chat-prefix")
    for it in traffic.generate(m, 3, 60.0, 1000):
        assert len(it.prompt) + it.max_new_tokens <= m["max_context"]
        assert m["budget"]["min"] <= it.max_new_tokens <= m["budget"]["max"]
        assert it.prompt.dtype == np.int32 and it.prompt.max() < 1000
    m = mix("docs-backlog")
    items = traffic.generate(m, 3, 0.0, 1000)
    assert len(items) == m["arrival"]["count"]
    assert all(it.due_s == 0.0 for it in items)


def test_sessions_share_system_prompt_and_history():
    m = mix("chat-prefix")
    items = traffic.generate(m, 11, 60.0, 1000)
    system = traffic.system_prompts(m, 11, 1000)
    by_session = defaultdict(list)
    for it in items:
        by_session[it.session].append(it)
    multi = 0
    for turns in by_session.values():
        turns.sort(key=lambda it: it.due_s)
        first = turns[0].prompt
        assert any(np.array_equal(first[:len(p)], p) for p in system)
        for prev, nxt in zip(turns, turns[1:]):
            multi += 1
            # The next turn holds the previous prompt, its answer, and more.
            n = len(prev.prompt)
            assert np.array_equal(nxt.prompt[:n], prev.prompt)
            assert len(nxt.prompt) > n + prev.max_new_tokens
            # Every whole page of the previous prompt is a page of the next.
            full = n // PAGE * PAGE
            assert np.array_equal(nxt.prompt[:full], prev.prompt[:full])
            assert nxt.due_s > prev.due_s
    assert multi > 10


@pytest.mark.parametrize("name", ["chat-prefix", "chat-prefix-sat"])
def test_open_loop_rate(name):
    m = mix(name)
    items = traffic.generate(m, 5, 200.0, 1000)
    starts = {}
    for it in items:
        starts.setdefault(it.session, it.due_s)
    rate = len(starts) / 200.0
    assert rate == pytest.approx(m["arrival"]["rate_per_s"], rel=0.15)


def test_saturated_mix_is_the_chat_mix_at_a_higher_rate():
    """The chat mix above its knee differs from the one kept for the tails
    cell in its arrival rate alone."""
    base, sat = mix("chat-prefix"), mix("chat-prefix-sat")
    assert sat["arrival"]["rate_per_s"] > base["arrival"]["rate_per_s"]
    sat["arrival"]["rate_per_s"] = base["arrival"]["rate_per_s"]
    assert sat == base
