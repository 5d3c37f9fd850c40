"""Spans and counters of the serve loop: the engine's phases recorded in a
profiler trace (on the device trace's clock), the queue-wait stamp of each
request, and the sidecar's back-pressure counters.  Tier-1."""
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import ServeConfig, TrainConfig, get_config
from repro.core.executor import BackgroundExecutor
from repro.serve.engine import PagedEngine
from repro.train.steps import init_train_state

PREFIXES = ("serve.", "kv.", "sidecar.")


@pytest.fixture(scope="module")
def tiny_engine_parts():
    cfg = get_config("repro-tiny")
    state = init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    return cfg, state["params"]


def _engine(cfg, params, **kw):
    scfg = dict(max_batch=4, max_seq_len=96, prefill_buckets=(8, 16, 32),
                page_size=8)
    scfg.update(kw)
    return PagedEngine(cfg, params, ServeConfig(**scfg))


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _traced(logdir, fn):
    """Run ``fn`` under a profiler trace; the program's spans it recorded,
    as (name, start_ns, end_ns, stats, thread line)."""
    jax.profiler.start_trace(str(logdir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(logdir.rglob("*.xplane.pb"))[-1]
    prof = ProfileData.from_file(str(path))
    return [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats),
             (plane.name, line.name))
            for plane in prof.planes for line in plane.lines
            for e in line.events if e.name.startswith(PREFIXES)]


def _inside(child, spans, parent_name):
    """Whether a span named ``parent_name`` on the child's thread covers it."""
    _, s, e, _, line = child
    return any(n == parent_name and ln == line and ps <= s and e <= pe
               for n, ps, pe, _, ln in spans)


def test_step_spans_nest_and_carry_rid(tiny_engine_parts, tmp_path):
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(0)
    eng = _engine(cfg, params)
    shared = _prompt(rng, cfg, 16)
    prompts = [np.concatenate([shared, _prompt(rng, cfg, 5 + i)])
               for i in range(3)]
    rids = []

    def serve():
        rids.extend(eng.submit(p, 4) for p in prompts)
        for _ in range(3):
            eng.step()

    spans = _traced(tmp_path, serve)
    eng.run()
    eng.close()
    names = [s[0] for s in spans]
    for name in ("serve.step", "serve.submit", "serve.admit",
                 "serve.admit.wait", "serve.decode", "serve.decode.wait",
                 "kv.match", "kv.alloc"):
        assert name in names, name
    nesting = {"serve.admit": "serve.step", "serve.decode": "serve.step",
               "serve.admit.wait": "serve.admit",
               "serve.decode.wait": "serve.decode",
               "kv.match": "serve.admit", "kv.alloc": "serve.admit"}
    for span in spans:
        if span[0] in nesting:
            assert _inside(span, spans, nesting[span[0]]), span
    admitted = sorted(st["rid"] for n, _, _, st, _ in spans
                      if n == "serve.admit")
    submitted = sorted(st["rid"] for n, _, _, st, _ in spans
                       if n == "serve.submit")
    assert admitted == submitted == sorted(rids)
    # One decode span per decode step, one step span per step() call.
    assert names.count("serve.step") == 3
    assert names.count("serve.decode") == names.count("serve.decode.wait")


def test_admitted_at_orders_queue_wait_for_deferred_admission(
        tiny_engine_parts):
    """A request whose first admission attempt defers (page shortage) is
    stamped by the attempt that succeeds, so its queue wait covers every
    step it spent deferred."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(3)
    # 13 usable pages; each request needs ceil((20+8)/8)=4 -> only 3 fit.
    eng = _engine(cfg, params, num_pages=14, cold_pages=0)
    rids = [eng.submit(_prompt(rng, cfg, 20), 8) for _ in range(6)]
    eng.step()
    after_first = time.time()
    assert eng.scheduler.depth() == 3 and eng.slots.free_count() == 1
    eng.run()
    for i, rid in enumerate(rids):
        req = eng.request(rid)
        assert req.submitted_at <= req.admitted_at <= req.first_token_at
        if i >= 3:                       # deferred at the first step
            assert req.admitted_at >= after_first
        out = eng.result(rid)
        assert out["queue_s"] == pytest.approx(
            req.admitted_at - req.submitted_at)
        assert 0.0 <= out["queue_s"] <= out["ttft_s"]
    sidecar = eng.stats()["sidecar"]
    assert sidecar["submitted"] >= len(rids)       # a record per request
    assert sidecar["blocked_submits"] >= 0 and sidecar["blocked_s"] >= 0.0
    eng.close()


def test_executor_blocked_submit_is_counted_and_spanned(tmp_path):
    """With the one queue place taken and the worker busy, a "block" submit
    waits in put(): counted once, with the seconds blocked, and spanned as
    sidecar.blocked."""
    ex = BackgroundExecutor(num_threads=1, max_inflight=1,
                            backpressure="block")
    gate, running = threading.Event(), threading.Event()

    def slow():
        running.set()
        gate.wait(5.0)

    def scenario():
        ex.submit("slow", slow)
        assert running.wait(5.0)
        ex.submit("queued", lambda: None)       # takes the one queue place
        t = threading.Thread(target=ex.submit,
                             args=("blocked", lambda: None))
        t.start()
        time.sleep(0.1)
        assert t.is_alive()                     # blocked in put()
        gate.set()
        t.join(timeout=5.0)
        assert not t.is_alive()

    spans = _traced(tmp_path, scenario)
    assert ex.drain(timeout=5.0)
    s = ex.stats()
    assert s["submitted"] == 3 and s["completed"] == 3
    assert s["blocked_submits"] == 1
    assert 0.05 <= s["blocked_s"] < 5.0
    assert [n for n, *_ in spans].count("sidecar.blocked") == 1
    ex.shutdown(drain=False)
